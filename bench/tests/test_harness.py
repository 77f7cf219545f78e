"""The harness end to end at a tiny size on the CPU.

  JAX_PLATFORMS=cpu python -m pytest -q bench/tests

A sound run reads ``correct``; the timed path broken underneath, in each
way a serving cell can break, reads not correct; the bfloat16 control
reads far wider than the program.  The chip check and the peaks table
are stood in for (``tiny.run``); everything else is the harness as the
benchmark runs it.
"""
import jax.numpy as jnp
import numpy as np
import pytest

import tiny  # noqa: F401  (puts bench/ and src/ on the path)
import serve


@pytest.mark.parametrize("mix", [tiny.CLOSED, tiny.OPEN],
                         ids=["closed", "open"])
def test_sound_run_is_correct(mix):
    out = tiny.run(mix)
    assert out["correct"], out["checks"]
    assert out["checks"]["target_rows_checked"]["value"] > 0
    assert out["checks"]["draft_rows_checked"]["value"] > 0
    assert set(out["metrics"]) == {"tokens_per_s", "setup_s"}
    assert list(out)[-1] == "checks"


def _roll(v):
    """Slot 0's logits shifted by one along the vocabulary."""
    return v.at[0].set(jnp.roll(v[0], 1, axis=-1))


def _drop_half(v):
    """Half of the batch left out: the upper half of the slot rows get
    the first row's logits instead of their own."""
    half = max(v.shape[0] // 2, 1)
    return v.at[half:].set(jnp.broadcast_to(v[:1], v[half:].shape))


def token_altered(executor, engine):
    """A token altered where it is produced: the target's verify logits
    of slot 0 come out shifted."""
    verify = executor.verify_rows
    executor.verify_rows = lambda *a: (lambda v, d: (_roll(v), d))(*verify(*a))


def half_batch_dropped(executor, engine):
    verify = executor.verify_rows
    executor.verify_rows = lambda *a: (lambda v, d: (_drop_half(v), d))(
        *verify(*a))


def commit_skipped(executor, engine):
    """A step that returns its state unchanged: the committed root never
    reaches the KV cache."""
    executor.commit_rows = lambda model_len, commit_mask: None


def draft_altered(executor, engine):
    """The draft's work cut short: its verify logits of slot 0 come out
    shifted (the served tokens are the target's, so only the draft's own
    check can see this)."""
    verify = executor.verify_rows
    executor.verify_rows = lambda *a: (lambda v, d: (v, _roll(d)))(*verify(*a))


def committed_token_altered(executor, engine):
    """The committed token itself altered after the pick."""
    inner = engine.inner
    apply = inner.exit_apply

    def exit_apply(st, *args, **kwargs):
        n = apply(st, *args, **kwargs)
        st.committed[-1] = (st.committed[-1] + 1) % tiny.SHAPE["vocab_size"]
        return n

    inner.exit_apply = exit_apply


@pytest.mark.parametrize("fault", [token_altered, half_batch_dropped,
                                   commit_skipped, draft_altered,
                                   committed_token_altered],
                         ids=lambda f: f.__name__)
def test_broken_path_is_not_correct(monkeypatch, fault):
    real = serve.build

    def build(*args, **kwargs):
        executor, engine = real(*args, **kwargs)
        fault(executor, engine)
        return executor, engine

    monkeypatch.setattr(serve, "build", build)
    out = tiny.run(tiny.CLOSED)
    assert not out["correct"], out["checks"]
    failed = [k for k, c in out["checks"].items() if not c["ok"]]
    assert failed and all(out["checks"][k]["value"] >
                          out["checks"][k]["limit"] for k in failed)


def test_control_reads_wider_than_the_program():
    """The bfloat16 control at the tiny size: on each role, its KL from
    the fp32 reference exceeds the limit and the program's reading (the
    CPU computes the program's fp32 matmuls exactly) by far."""
    conf = tiny.conf()
    conf["vocab_size"] = conf["draft"]["vocab_size"] = 4096
    mix = {**tiny.CLOSED, "output_tokens": {"median": 48, "sigma": 0.3,
                                            "min": 32, "max": 64}}
    out = tiny.run(mix, seconds=8.0, control=True, conf_=conf)
    assert out["correct"], out["checks"]
    for role in ("target", "draft"):
        stats = out["stats"][role]
        assert stats["kl"]["rows"] >= 30
        limit = out["checks"][f"{role}_kl"]["limit"]
        assert stats["control_kl"]["max"] > 3 * limit > 3 * stats["kl"]["max"]


def test_schedule_is_the_same_work_for_every_seed():
    import traffic
    a = traffic.schedule(tiny.OPEN, 1, 512, 4, 10.0)
    b = traffic.schedule(tiny.OPEN, 2**33 + 5, 512, 4, 10.0)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt)
                                                      for r in b)
    assert sorted(r.new_tokens for r in a) == sorted(r.new_tokens
                                                     for r in b)
    assert np.isclose(max(r.due_s for r in a), max(r.due_s for r in b))
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]


def test_find_cell_takes_a_held_out_cell():
    import run
    bench = run.load_benchmark()
    for cell in bench["workloads"]:
        assert run.find_cell(bench, cell["name"]) is cell
    held = run.find_cell(bench, "qwen25-32b:code-poisson")
    assert (held["config"], held["traffic"], held["chips"]) == (
        "qwen25-32b", "code-poisson", 1)
    with pytest.raises(KeyError):
        run.find_cell(bench, "no-such-cell")
