"""Bring-up smoke of the SpecPipe-DB serving path on a TPU.

  python chip_smoke.py             # one chip
  python chip_smoke.py --chips 4   # the 4-stage pipeline on four chips

One chip: the paper's pair (``configs/pipedec_pair.py``: Llama-3.1-70B
target, Llama-3.2-1B draft) at its published widths in fp32 with random
seeded weights, depth cut to 1 target and 2 draft layers so the weights
fit the chip's 16 GB.  The bundles and the engine come from
``repro.launch.serve``'s own construction; the requests go through
``ServingEngine(mode="pipedec-db")`` on the local fused executor.  Checks:
every request returns its tokens, every verify and prefill logit is
finite, the served prefill logits agree with ``tf.forward`` at full fp32
matmul precision, and every Pallas kernel (interpret mode off) agrees
with its oracle at the target's head shape.

Four chips: 4 target layers, one per stage, each created on its own chip
(embedding on the first, head on the last).  The overlapped ring and the
async actors run one after the other, then the flush executor as their
reference; the first verify step's logits must agree.

Times printed on earlier lines are one run's wall clock (smoke, not a
benchmark).  The last line is one JSON object, ``{"ok": ..., "device":
{"platform", "kind", "count"}}``; ``"ok": false`` and a non-zero exit
when a phase fails or no TPU is attached — nothing falls back to the CPU.
"""
from __future__ import annotations

import argparse
import collections
import gc
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))

# Relative error bound, max|got - ref| / max|ref|, for every comparison
# below.  A TPU runs fp32 matmuls as one bf16 pass unless told otherwise
# (unit roundoff 2**-9 ~ 2e-3 per operand).  Over the ~10 matmuls between
# embedding and logits, taken at the worst of ~5e5 logits (about 5 sigma),
# that compounds to ~1e-2; a wrong layer, mask, cache row or block index
# moves values by O(1) of their range.
REL_TOL = 2e-2


def say(*parts) -> None:
    print(*parts, flush=True)


def check(ok, what: str) -> None:
    """A failed check fails its phase (not ``assert``: it survives -O)."""
    if not ok:
        raise RuntimeError(what)


def rel_err(got, ref) -> float:
    import numpy as np
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(got - ref)) / max(np.max(np.abs(ref)), 1e-30))


def bytes_per_device(*trees):
    """Bytes of every array in ``trees`` by device id."""
    import jax
    out = collections.Counter()
    for tree in trees:
        for leaf in jax.tree.leaves(tree):
            for shard in leaf.addressable_shards:
                out[shard.device.id] += shard.data.nbytes
    return dict(sorted(out.items()))


def peak_bytes(devices):
    stats = {d.id: (d.memory_stats() or {}) for d in devices}
    return {i: s.get("peak_bytes_in_use") for i, s in stats.items()}


def gib(n) -> str:
    return "n/a" if n is None else f"{n / 2**30:.3f} GiB"


class CompileClock:
    """Backend-compile seconds and persistent-cache hits/misses, from
    JAX's own monitoring events."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.seconds = 0.0
        self.events = collections.Counter()
        compile_event = dispatch.BACKEND_COMPILE_EVENT

        def on_duration(event, duration, **_):
            if event == compile_event:
                self.seconds += duration

        def on_event(event, **_):
            if event.startswith("/jax/compilation_cache/cache_"):
                self.events[event.rsplit("/", 1)[-1]] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def report(self, label: str) -> None:
        say(f"{label}: backend compile {self.seconds:.2f} s, compile cache "
            f"hits {self.events['cache_hits']} misses "
            f"{self.events['cache_misses']}")


def watch_logits(executor):
    """Record every verify/prefill logit the executor hands the engine
    (finiteness is checked on device, one flag per call) and the first
    verify step's entering root tokens and logits."""
    import jax.numpy as jnp
    import numpy as np
    seen = {"finite": [], "prefill": [], "first": None}
    overlapped = getattr(executor, "overlapped", False)
    name = "tick_rows" if overlapped else "verify_rows"
    verify, prefill = getattr(executor, name), executor.prefill

    def on_verify(tokens, positions, masks, model_len, write_idx, row_on):
        out = verify(tokens, positions, masks, model_len, write_idx, row_on)
        draft, target = out if overlapped else out[::-1]
        if draft is not None and not overlapped:
            seen["finite"].append(jnp.isfinite(draft).all())
        if not overlapped:
            seen["finite"].append(jnp.isfinite(target).all())
        if seen["first"] is None and np.any(row_on):
            seen["first"] = (np.asarray(tokens)[:, 0].copy(),
                             np.nonzero(np.asarray(row_on))[0], target)
            seen["mask_shape"] = np.shape(masks)
        return out

    def on_prefill(slot, prompt):
        logits = prefill(slot, prompt)
        seen["finite"].append(jnp.isfinite(logits).all())
        seen["prefill"].append((np.asarray(prompt).reshape(-1), logits))
        return logits

    setattr(executor, name, on_verify)
    executor.prefill = on_prefill
    return seen


def first_root_logits(seen):
    """{slot: (root token, root-row target logits)} of the first verify
    step (futures of the deferred executors are resolved by then)."""
    import numpy as np
    tokens, slots, target = seen["first"]
    rows = {}
    for s in slots:
        s = int(s)
        if isinstance(target, dict):
            if target[s].dead:          # pruned before its exit
                continue
            row = target[s].resolve()
        else:
            row = target[s]
        rows[s] = (int(tokens[s]), np.asarray(row[0]))
    return rows


def serve_once(engine, args, vocab):
    """Submit the CLI's requests, run them, return (results, seconds)."""
    import jax
    from repro.launch import serve
    serve.submit_requests(engine, args, vocab)
    t0 = time.perf_counter()
    results = engine.run()
    jax.block_until_ready([r.tokens for r in results.values()])
    return results, time.perf_counter() - t0


def check_results(results, args, vocab):
    check(len(results) == args.requests,
          f"{len(results)} of {args.requests} requests returned")
    for uid, res in results.items():
        toks = res.tokens
        check(len(toks) >= args.new_tokens,
              f"request {uid} returned {len(toks)} tokens")
        check(((0 <= toks) & (toks < vocab)).all(), f"request {uid} ids")


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------
def phase_serve_one_chip(serve_argv, clock):
    """Serve through the CLI's own construction; returns the bundle pair
    for the reference phase."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch import serve
    from repro.models import attention as attn_mod
    from repro.models import transformer as tf

    args = serve.parse_args(serve_argv)
    t0 = time.perf_counter()
    target, draft = serve.build_bundles(args)
    jax.block_until_ready((target.params, draft.params))
    executor, engine = serve.build_engine(args, target, draft)
    setup_s = time.perf_counter() - t0
    tcfg, dcfg = target.cfg, draft.cfg
    say(f"depth cut: target {tcfg.name} {tcfg.num_layers} layer(s), draft "
        f"{dcfg.name} {dcfg.num_layers} layer(s); widths as published "
        f"(d_model {tcfg.d_model}/{dcfg.d_model}, heads {tcfg.num_heads}/"
        f"{dcfg.num_heads}, kv heads {tcfg.num_kv_heads}/"
        f"{dcfg.num_kv_heads}, d_ff {tcfg.d_ff}/{dcfg.d_ff}, vocab "
        f"{tcfg.vocab_size}), fp32")
    held = bytes_per_device(target.params, draft.params)
    say(f"weights: target {tcfg.num_layers} + draft {dcfg.num_layers} "
        f"layers on each device; bytes per device "
        f"{ {d: gib(b) for d, b in held.items()} }")
    say(f"set-up (weight init, smoke): {setup_s:.2f} s")

    seen = watch_logits(executor)
    vocab = tcfg.vocab_size
    first, first_s = serve_once(engine, args, vocab)
    check_results(first, args, vocab)
    clock.report("after the first serve")
    again, warm_s = serve_once(engine, args, vocab)
    check_results(again, args, vocab)
    check(all(np.array_equal(first[u].tokens, again[u].tokens)
              for u in first), "greedy serving is not deterministic")
    check(bool(jnp.all(jnp.stack(seen["finite"]))), "non-finite logits")
    # the fused tree verify the executor dispatches every timestep,
    # compiled ahead to read its memory plan
    t_cache, _, t_tree, _ = executor.arena.stacked
    b, w, tcap = seen["mask_shape"]
    zeros = lambda *s: jnp.zeros(s, jnp.int32)
    compiled = target._tree_verify_rows.lower(
        target.params, node_tokens=zeros(b, w), node_positions=zeros(b, w),
        tree_mask=jnp.zeros((b, w, tcap), bool), cache=t_cache,
        cache_len=zeros(b), tree_caches=t_tree, tree_write_index=zeros(b),
        bucket=b).compile()
    mem = compiled.memory_analysis()
    say(f"memory_analysis (target tree verify, bucket {b}): arguments "
        f"{gib(mem.argument_size_in_bytes)}, outputs "
        f"{gib(mem.output_size_in_bytes)}, temps "
        f"{gib(mem.temp_size_in_bytes)}, aliased "
        f"{gib(mem.alias_size_in_bytes)}")

    stats = engine.db_stats
    n_tok = sum(len(r.tokens) for r in again.values())
    say(f"serve (smoke, not a benchmark): {args.requests} requests x "
        f"{args.new_tokens} new tokens, {n_tok} tokens returned; first run "
        f"{first_s:.2f} s (compiles included), second run {warm_s:.2f} s, "
        f"{stats.timesteps} timesteps, acceptance "
        f"{stats.acceptance_rate:.3f}, tokens/timestep "
        f"{stats.tokens_per_timestep:.3f}")
    say(f"attention path: "
        f"{'Pallas kernels' if attn_mod.USE_PALLAS_ATTN else 'jnp'} "
        f"(USE_PALLAS_ATTN={attn_mod.USE_PALLAS_ATTN})")

    # reference: the served prefill logits against the plain forward pass
    # at full fp32 matmul precision
    with jax.default_matmul_precision("highest"):
        ref_fwd = jax.jit(lambda p, t: tf.forward(p, tcfg, t)[0])
        errs, agree = [], 0
        for prompt, logits in seen["prefill"][:args.requests]:
            ref = ref_fwd(target.params, jnp.asarray(prompt)[None])[:, -1]
            errs.append(rel_err(logits, ref))
            agree += int(jnp.argmax(logits)) == int(jnp.argmax(ref))
    worst = max(errs)
    say(f"prefill logits vs tf.forward (highest precision), "
        f"{len(errs)} prompts: max rel err {worst:.3e} (tolerance "
        f"{REL_TOL:.0e}); next-token argmax agrees on {agree}")
    check(worst <= REL_TOL, "served prefill logits off the reference")
    return target, draft


def phase_kernels(shape):
    """Each Pallas kernel once, interpret off, against its oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import interpret_mode, ref
    from repro.kernels.flash import flash_attention_lse
    from repro.kernels.paged import (paged_flash_attention_lse,
                                     paged_tree_block_attention)
    from repro.kernels.quant import dequant_matmul_kernel, quantize_weight
    from repro.kernels.tree_block import tree_block_attention

    b, h, kv, hd, n, lmax, t, page = shape
    say(f"kernels: interpret mode {interpret_mode()}, shape B{b} H{h} "
        f"KV{kv} hd{hd} n{n} L{lmax} T{t} page{page}")
    check(not interpret_mode(), "a Pallas kernel would interpret here")
    rng = np.random.default_rng(0)
    arr = lambda *s: jnp.asarray(rng.normal(size=s).astype(np.float32))
    q = arr(b, h, n, hd)
    k, v = arr(b, kv, lmax, hd), arr(b, kv, lmax, hd)
    kt, vt = arr(b, kv, t, hd), arr(b, kv, t, hd)
    plen = jnp.asarray(rng.integers(1, lmax, size=b), jnp.int32)
    mask = jnp.asarray(rng.random((b, n, t)) > 0.4).at[:, :, 0].set(True)
    # paged views of the same data: slot i's logical block j sits at
    # physical block 1 + i * mb + j (block 0 is the null block)
    mb, mbt = lmax // page, -(-t // page)

    def pool(x, blocks):
        x = jnp.pad(x, ((0, 0), (0, 0), (0, blocks * page - x.shape[2]),
                        (0, 0)))
        x = x.reshape(b, kv, blocks, page, hd).transpose(0, 2, 1, 3, 4)
        return jnp.concatenate([jnp.zeros((1, kv, page, hd)),
                                x.reshape(b * blocks, kv, page, hd)])

    table = 1 + jnp.arange(b * mb, dtype=jnp.int32).reshape(b, mb)
    ttable = 1 + jnp.arange(b * mbt, dtype=jnp.int32).reshape(b, mbt)
    rep = h // kv
    dec_ref = ref.decode_attention_ref
    x = arr(b * n, h * hd)
    wq = quantize_weight(arr(h * hd, h * hd), 1)

    def tree_ref():
        kr, vr = jnp.repeat(kt, rep, 1), jnp.repeat(vt, rep, 1)
        s = jnp.einsum("bhnd,bhtd->bhnt", q, kr) / np.sqrt(hd)
        s = jnp.where(mask[:, None], s, -jnp.inf)
        return jnp.einsum("bhnt,bhtd->bhnd", jax.nn.softmax(s, -1), vr)

    # (kernel as served, oracle) pairs; the oracles run at full precision
    cases = (
        ("flash", lambda: flash_attention_lse(q, k, v, plen)[0],
         lambda: dec_ref(q, k, v, plen.reshape(-1, 1, 1, 1))),
        ("tree_block", lambda: tree_block_attention(q, kt, vt, mask)[0],
         tree_ref),
        ("paged_flash", lambda: paged_flash_attention_lse(
            q, pool(k, mb), pool(v, mb), table, plen)[0],
         lambda: ref.paged_decode_attention_ref(
             q, pool(k, mb), pool(v, mb), table, plen)),
        ("paged_tree", lambda: paged_tree_block_attention(
            q, pool(kt, mbt), pool(vt, mbt), ttable, mask)[0], tree_ref),
        ("dequant_matmul",
         lambda: dequant_matmul_kernel(x, wq["q8"], wq["scale"]),
         lambda: ref.dequant_matmul_ref(x, wq["q8"], wq["scale"])),
    )
    worst = 0.0
    for name, kernel, oracle in cases:
        got = kernel()
        with jax.default_matmul_precision("highest"):
            want = oracle()
        err = rel_err(got, want)
        worst = max(worst, err)
        say(f"kernel {name}: max rel err vs oracle {err:.3e} "
            f"(tolerance {REL_TOL:.0e})")
        check(err <= REL_TOL, f"kernel {name} off its oracle")
    return worst


def phase_four_chips(serve_argv):
    """Overlapped ring and async actors against the flush executor, one
    stage per chip, on one placed target."""
    import jax
    import numpy as np
    from repro.launch import pipeline as pl
    from repro.launch import serve

    base = serve.parse_args(serve_argv + ["--executor", "sharded"])
    mesh = serve.stage_mesh(base)
    check(mesh is not None and mesh.shape["model"] == 4, "needs 4 chips")
    devs = pl.stage_devices(mesh)
    t0 = time.perf_counter()
    target, draft = serve.build_bundles(base, mesh)
    jax.block_until_ready((target.params, draft.params))
    say(f"set-up (placed weight init, smoke): "
        f"{time.perf_counter() - t0:.2f} s")
    cfg = target.cfg
    total = sum(x.nbytes for x in jax.tree.leaves(target.params))
    say(f"depth cut: target {cfg.name} {cfg.num_layers} layers (one per "
        f"stage), draft {draft.cfg.name} {draft.cfg.num_layers} layers; "
        f"whole target {gib(total)}")
    held = bytes_per_device(target.params, draft.params)
    for k, d in enumerate(devs):
        what = [f"target layer {k}"] + (["embed", "draft"] if k == 0 else []) \
            + (["final_norm", "lm_head"] if k == len(devs) - 1 else [])
        say(f"chip {d.id} (stage {k}): {', '.join(what)}; "
            f"{gib(held.get(d.id, 0))}")

    runs = {}
    for name, extra in (("overlapped", ["--executor", "sharded",
                                        "--overlap"]),
                        ("async", ["--executor", "async"]),
                        ("flush", ["--executor", "sharded"])):
        args = serve.parse_args(serve_argv + extra)
        executor, engine = serve.build_engine(args, target, draft, mesh)
        seen = watch_logits(executor)
        try:
            results, secs = serve_once(engine, args, cfg.vocab_size)
            check_results(results, args, cfg.vocab_size)
            roots = first_root_logits(seen)
        finally:
            if name == "async":
                executor.shutdown()
        stats = engine.db_stats
        say(f"{name} (smoke, not a benchmark): {secs:.2f} s incl. "
            f"compiles, {stats.timesteps} timesteps, acceptance "
            f"{stats.acceptance_rate:.3f}; peak bytes per chip "
            f"{ {i: gib(p) for i, p in peak_bytes(devs).items()} }")
        runs[name] = ({u: r.tokens for u, r in results.items()}, roots)
        del executor, engine, seen
        gc.collect()

    ref_tokens, ref_roots = runs["flush"]
    ok = True
    for name in ("overlapped", "async"):
        tokens, roots = runs[name]
        same = [s for s in roots if s in ref_roots
                and roots[s][0] == ref_roots[s][0]]
        errs = [rel_err(roots[s][1], ref_roots[s][1]) for s in same]
        agree = sum(np.array_equal(tokens[u], ref_tokens[u])
                    for u in ref_tokens)
        worst = max(errs) if errs else float("inf")
        say(f"{name} vs flush, first verify step: {len(same)} slot(s) "
            f"with the same root token, max rel err {worst:.3e} "
            f"(tolerance {REL_TOL:.0e}); token agreement {agree}/"
            f"{len(ref_tokens)} requests (reported, not gated)")
        ok &= bool(errs) and worst <= REL_TOL
    check(ok, "pipeline executors disagree with the flush reference")
    peaks = peak_bytes(devs)
    say(f"peak bytes per chip {({i: gib(p) for i, p in peaks.items()})} "
        f"against the whole target's {gib(total)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the 4-stage pipeline path and its "
                         "flush reference")
    opts = ap.parse_args(argv)
    device = {}
    failed = []
    try:
        import jax
        devs = jax.devices()
        device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
                  "count": len(devs)}
        say(f"devices: {device}")
        if device["platform"] != "tpu":
            raise RuntimeError("no TPU attached: this smoke never runs on "
                               f"{device['platform']}")
        if device["count"] < opts.chips:
            raise RuntimeError(f"--chips {opts.chips} but {device['count']} "
                               f"device(s) attached")
        sys.path.insert(0, os.path.join(ROOT, "src"))
        from repro.launch.compile_cache import enable_compile_cache
        say(f"compile cache: {enable_compile_cache()}")
    except Exception:
        traceback.print_exc()
        print(json.dumps({"ok": False, "device": device}), flush=True)
        return 1

    clock = CompileClock()
    serve_argv = ["--mode", "pipedec-db", "--no-smoke", "--requests", "4",
                  "--new-tokens", "16"]
    if opts.chips == 4:
        phases = [("four_chips", lambda: phase_four_chips(
            serve_argv + ["--target-layers", "4", "--draft-layers", "2"]))]
    else:
        phases = [("serve", lambda: phase_serve_one_chip(
            serve_argv + ["--target-layers", "1", "--draft-layers", "2"],
            clock)),
            ("kernels", lambda: phase_kernels(
                (4, 64, 8, 128, 8, 512, 73, 16)))]
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            run()
            say(f"phase {name}: ok in {time.perf_counter() - t0:.2f} s")
        except Exception:
            traceback.print_exc()
            say(f"phase {name}: FAILED")
            failed.append(name)
        gc.collect()
    clock.report("total")
    say(f"peak bytes per device "
        f"{ {i: gib(p) for i, p in peak_bytes(jax.devices()).items()} }")
    ok = not failed
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
