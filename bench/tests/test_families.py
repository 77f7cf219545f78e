"""The model-family seam (``model.family``, ``bench/families/``).

  JAX_PLATFORMS=cpu python -m pytest -q bench/tests/test_families.py

Pins: what the Qwen2 family makes and counts, recorded on the harness
before Qwen2 moved into ``bench/families/qwen2.py``, must stay bit for
bit: the seeded weights and the reference's logits at ``tiny.py``'s
shapes (CPU), and the operations and bytes at the ``qwen25-7b`` shapes.

Seam: a family module that exists only in a temporary directory, a
Qwen2 copy under another ``model_type`` that counts its hooks, serves a
whole run and reads ``correct``, with no file of the harness edited.
"""
import collections
import dataclasses
import hashlib
import json
import os
import re

import jax
import numpy as np
import pytest

import tiny  # noqa: F401  (puts bench/ and src/ on the path)
import model
import reference
import run

SEED = 2**33 + 5
SEQ = (np.arange(37) * 7 + 3) % tiny.SHAPE["vocab_size"]

PINNED_WEIGHTS = {
    "target": "617cd99739f2c0df274de2fd9d7a6232f9c2e5bf41b82441e9af09c3a190b0d5",
    "draft": "d13a49d46fa277a395a97232b339d32e98c0eb8e95310dc74be7a9d514b5e4a4",
}
PINNED_LOGITS = {
    ("target", "float32"):
        "dbc10dccd8a5709fb6743f3ab0662d2fa5de24e882edd1d20598559fd6e680a1",
    ("target", "bfloat16"):
        "1ab90ddf94e44ea27408c1e7739dca676fcde17a7643b46d54ede1dac4c9204b",
    ("draft", "float32"):
        "0d35161dd4c74f21f8e791235c0cd27e4335f8b441a12ff77af19fa9ffd9f21b",
    ("draft", "bfloat16"):
        "383ce4d00f64a3186866ce1c7c5ee9a903500fc114db380a1148f0350f75e4a6",
}
# (nodes, context, kv_rows) of a verify call, and prompt lengths
VERIFY_IN = ((1, 64.0, 65.0), (128, 131072.5, 16500.0), (77, 98765.0, 40001.0))
PREFILL_IN = (1, 64, 1024, 2048)
PINNED_COUNTS = {
    "target": {
        "verify": [(2958032896.0, 5910601728.0),
                   (385674670080.0, 6257121280.0),
                   (233149521920.0, 6611140608.0)],
        "prefill": [2954420224.0, 120528830464.0, 1940297089024.0,
                    3939633725440.0]},
    "draft": {
        "verify": [(993656832.0, 1978795520.0),
                   (137757763584.0, 2459950592.0),
                   (84583059456.0, 3006490112.0)],
        "prefill": [988237824.0, 46253211648.0, 778242490368.0,
                    1646406795264.0]},
}
# ``dataclasses.asdict`` of the program's ModelConfig, as sorted JSON
PINNED_PROGRAM_CONFIG = {
    "target": "acb4327fce113040f60b4a5244d3d9f6731a993e70fd67540e0167ca8414769c",
    "draft": "90c88c334a9ed9f4fdeb6739cd5e6e4028eae19e829d46eeb6e8db365fad6414",
}


def digest(tree) -> str:
    """sha256 over each leaf's path, type, shape and bytes."""
    h = hashlib.sha256()
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x = np.asarray(x)
        for part in (jax.tree_util.keystr(path), str(x.dtype), str(x.shape)):
            h.update(part.encode())
        h.update(x.tobytes())
    return h.hexdigest()


@pytest.fixture(scope="module")
def tiny_weights():
    target, draft = run.make_weights(tiny.conf(), SEED, jax.devices()[:1])
    return {"target": target, "draft": draft}


@pytest.mark.parametrize("role", ["target", "draft"])
def test_weights_are_pinned(tiny_weights, role):
    assert digest(tiny_weights[role].params) == PINNED_WEIGHTS[role]


@pytest.mark.parametrize("role,precision", sorted(PINNED_LOGITS))
def test_reference_logits_are_pinned(tiny_weights, role, precision):
    w = tiny_weights[role]
    x = reference.hidden(w, SEQ, precision)[np.arange(len(SEQ))]
    got = reference.logits(w, x, precision)
    assert got.shape == (len(SEQ), tiny.SHAPE["vocab_size"])
    assert digest(got) == PINNED_LOGITS[(role, precision)]


@pytest.mark.parametrize("role", ["target", "draft"])
def test_counts_are_pinned(role):
    conf = model.load_config("qwen25-7b")
    i = ("target", "draft").index(role)
    fam, s = model.families(conf)[i], model.shapes(conf)[i]
    assert [fam.verify_call(s, s.layers, *a) for a in VERIFY_IN] == \
        PINNED_COUNTS[role]["verify"]
    assert [fam.prefill_flops(s, n) for n in PREFILL_IN] == \
        PINNED_COUNTS[role]["prefill"]


@pytest.mark.parametrize("role", ["target", "draft"])
def test_program_config_is_pinned(role):
    conf = model.load_config("qwen25-7b")
    i = ("target", "draft").index(role)
    cfg = model.families(conf)[i].program_config(model.shapes(conf)[i], role)
    text = json.dumps(dataclasses.asdict(cfg), sort_keys=True)
    assert hashlib.sha256(text.encode()).hexdigest() == \
        PINNED_PROGRAM_CONFIG[role]


# -- the seam ----------------------------------------------------------------

HOOKS = ("make_local", "program_config", "hidden", "head", "verify_call",
         "prefill_flops")
PROBE = """

# -- the probe: every hook counted --------------------------------------------
import collections as _collections

CALLS = _collections.Counter()


def _counted(_name, _fn):
    def call(*args, **kwargs):
        CALLS[_name] += 1
        return _fn(*args, **kwargs)
    return call


for _name in {hooks!r}:
    globals()[_name] = _counted(_name, globals()[_name])
"""


def harness_files():
    """Every file of the harness with its bytes' digest."""
    out = {}
    for d, dirs, files in os.walk(model.BENCH):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in files:
            path = os.path.join(d, f)
            with open(path, "rb") as fh:
                out[path] = hashlib.sha256(fh.read()).hexdigest()
    return out


@pytest.fixture
def probe_family(tmp_path, monkeypatch):
    """``qwen2_probe``: the Qwen2 family copied to a directory put first
    on the lookup path, with a counter in each hook."""
    with open(os.path.join(model.BENCH, "families", "qwen2.py")) as f:
        source = f.read()
    (tmp_path / "qwen2_probe.py").write_text(
        source + PROBE.format(hooks=HOOKS))
    monkeypatch.setattr(model, "FAMILIES", [str(tmp_path), *model.FAMILIES])
    return tmp_path


def test_a_new_family_module_serves_a_run(probe_family):
    before = harness_files()
    conf = tiny.conf()
    conf["model_type"] = "qwen2_probe"
    target_fam, draft_fam = model.families(conf)
    assert target_fam.__file__ == str(probe_family / "qwen2_probe.py")
    assert draft_fam.__file__ == os.path.join(model.BENCH, "families",
                                              "qwen2.py")
    out = tiny.run(tiny.CLOSED, conf_=conf)
    assert out["correct"], out["checks"]
    assert out["checks"]["target_rows_checked"]["value"] > 0
    calls = target_fam.CALLS
    assert all(calls[h] > 0 for h in HOOKS), calls
    # the draft stays Qwen2: the probe made and configured the target only
    assert calls["make_local"] == 1 and calls["program_config"] == 1
    assert harness_files() == before


CONFIG_FAULTS = {
    "target_without_model_type": lambda c: c.pop("model_type"),
    "draft_without_model_type": lambda c: c["draft"].pop("model_type"),
    "unknown_model_type": lambda c: c.update(model_type="no_such_family"),
}


@pytest.mark.parametrize("fault", sorted(CONFIG_FAULTS))
def test_a_config_without_a_family_fails_at_load(tmp_path, monkeypatch,
                                                 fault):
    conf = tiny.conf()
    CONFIG_FAULTS[fault](conf)
    (tmp_path / "configs").mkdir()
    path = tmp_path / "configs" / "bad.json"
    path.write_text(json.dumps(conf))
    monkeypatch.setattr(model, "BENCH", str(tmp_path))
    with pytest.raises(LookupError, match=re.escape(str(path))) as err:
        model.load_config("bad")
    if fault == "unknown_model_type":
        assert os.path.join(model.FAMILIES[0], "no_such_family.py") in \
            str(err.value)
    with pytest.raises(LookupError):
        model.shapes(conf)


def test_a_family_is_loaded_once(probe_family):
    """Every lookup of a family returns the one module it loaded."""
    c = tiny.conf()
    c["model_type"] = "qwen2_probe"
    first = model.family(c, "x")
    assert model.family(c, "y") is first
    assert isinstance(first.CALLS, collections.Counter)
