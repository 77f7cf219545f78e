"""Serving driver: load (or randomly init) target + draft, run a batch of
requests through the ServingEngine in pp, pipedec, or pipedec-db mode.

  PYTHONPATH=src python -m repro.launch.serve --mode pipedec --requests 4

The pair defaults to the reduced smoke widths; ``--no-smoke`` serves the
paper's published widths (Llama-3.1-70B target, Llama-3.2-1B draft), and
``--target-layers`` / ``--draft-layers`` cut their depth to fit a chip:

  PYTHONPATH=src python -m repro.launch.serve --mode pipedec-db \
      --no-smoke --target-layers 1 --draft-layers 2

SpecPipe-DB on the sharded pipeline deployment (one stage per device;
combine with XLA_FLAGS=--xla_force_host_platform_device_count=8 on CPU):

  PYTHONPATH=src python -m repro.launch.serve --mode pipedec-db \
      --executor sharded --requests 4

``--overlap`` selects the steady-state overlapped schedule (persistent
always-full ring, ONE tick per global timestep, deferred exit logits,
in-ring pruning propagation) instead of the per-timestep flush; the
PipeDec stage count is then the mesh's device count, since the ring IS
the flight bookkeeping:

  PYTHONPATH=src python -m repro.launch.serve --mode pipedec-db \
      --executor sharded --overlap --requests 4

``--executor async`` replaces the host-lockstep tick entirely:
free-running per-stage actor threads (one per stage/device) plus a
disaggregated draft actor, bit-identical greedy tokens to the lockstep
backends:

  PYTHONPATH=src python -m repro.launch.serve --mode pipedec-db \
      --executor async --stages 4 --requests 4
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import numpy as np

from repro import configs as cfg_reg
from repro.checkpoint import load_pytree
from repro.core.pipedec import PipeDecConfig
from repro.core.speculative import ModelBundle
from repro.launch import pipeline as pl
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as tf
from repro.serving import Request, ServingEngine

MAX_LEN = 512
PROMPT_LEN = 8


def build_bundle(arch: str, *, smoke: bool, seed: int, ckpt: str = "",
                 vocab_floor: int = 0, layers: int = 0, stage_mesh=None):
    """Init (or load from ``ckpt``) one arch and wrap it as a
    ``ModelBundle`` with jitted prefill/decode/tree_verify.

    ``layers`` cuts the depth (0 keeps the config's; widths never
    change).  Random weights are created by one compiled program, so a
    full-width layer is written once, without eager temporaries.  With
    ``stage_mesh`` the weights are created already placed over the
    one-stage-per-device mesh (``launch.pipeline.init_stage_placed``):
    no device ever holds the whole model.
    """
    cfg = cfg_reg.get_config(arch, smoke=smoke)
    if layers:
        cfg = dataclasses.replace(cfg, num_layers=layers)
    if vocab_floor and cfg.vocab_size < vocab_floor:
        cfg = dataclasses.replace(cfg, vocab_size=vocab_floor)
    key = jax.random.PRNGKey(seed)
    if ckpt:
        params = load_pytree(ckpt)["params"]
    elif stage_mesh is not None:
        params = pl.init_stage_placed(key, cfg, stage_mesh)
    else:
        params = jax.jit(tf.init_model, static_argnums=1)(key, cfg)
    return ModelBundle(params, cfg)


def parse_args(argv=None):
    """The serving CLI's options (``main`` and ``chip_smoke.py`` share
    them)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", choices=["pp", "pipedec", "pipedec-db"],
                    default="pipedec")
    ap.add_argument("--executor", choices=["local", "sharded", "async"],
                    default="local",
                    help="pipedec-db compute backend (sharded = one "
                         "pipeline stage per mesh device; async = "
                         "free-running per-stage actor threads + a "
                         "disaggregated draft actor, no host lockstep)")
    ap.add_argument("--overlap", action="store_true",
                    help="sharded executor only: steady-state overlapped "
                         "schedule (one ring tick per timestep with "
                         "deferred exit logits) instead of the "
                         "per-timestep flush; forces --stages to the "
                         "device count")
    ap.add_argument("--target-arch", default="pipedec-target")
    ap.add_argument("--draft-arch", default="pipedec-draft")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="reduced smoke widths (default); --no-smoke "
                         "serves the published widths")
    ap.add_argument("--target-layers", type=int, default=0,
                    help="cut the target to this many layers (0 = all)")
    ap.add_argument("--draft-layers", type=int, default=0,
                    help="cut the draft to this many layers (0 = all)")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--stages", type=int, default=4)
    ap.add_argument("--width", type=int, default=8)
    ap.add_argument("--branch", type=int, default=4)
    ap.add_argument("--slots", type=int, default=3)
    ap.add_argument("--quant", choices=["none", "int8"], default="none",
                    help="int8: serve both bundles quantized "
                         "(ModelBundle.quantize() — per-out-channel int8 "
                         "weights + int8 KV arena, ~3x the slots per byte "
                         "budget; dense attention architectures only)")
    ap.add_argument("--paged", action="store_true",
                    help="pipedec-db only: block-paged KV arenas "
                         "(models.paging pools behind per-slot block "
                         "tables; the local backend's PagedKVArena backs "
                         "each request's horizon instead of max_len)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="rows per KV block under --paged (power of two)")
    return ap.parse_args(argv)


def stage_mesh(args):
    """The mesh a multi-device pipeline executor serves from (one stage
    per device), or None where the target lives whole on one device."""
    n = len(jax.devices())
    if args.mode != "pipedec-db" or args.executor == "local" or n == 1:
        return None
    return pl.make_stage_mesh(n)


def build_bundles(args, mesh=None):
    """Target + draft as the CLI serves them: the target placed over
    ``mesh`` when one is given, the draft whole on the default device."""
    target = build_bundle(args.target_arch, smoke=args.smoke, seed=0,
                          layers=args.target_layers, stage_mesh=mesh)
    draft = build_bundle(args.draft_arch, smoke=args.smoke, seed=1,
                         layers=args.draft_layers)
    if args.quant == "int8":
        target, draft = target.quantize(), draft.quantize()
    return target, draft


def build_engine(args, target, draft, mesh=None):
    """The executor (None for the batch modes) and the ``ServingEngine``
    the CLI runs."""
    if mesh is not None:
        # one pipeline stage per mesh device
        args.stages = mesh.shape["model"]
    elif args.overlap:
        # the overlapped ring length is pcfg.n_stages — it must equal the
        # mesh's stage count (one device per stage)
        args.stages = len(jax.devices())
    assert not args.overlap or (args.mode == "pipedec-db"
                                and args.executor == "sharded"), \
        "--overlap needs --mode pipedec-db --executor sharded"
    pcfg = PipeDecConfig(n_stages=args.stages, width=args.width,
                         branch=args.branch)
    executor = None
    if args.mode == "pipedec-db":
        from repro.serving import (AsyncPipelineExecutor,
                                   LocalFusedExecutor,
                                   OverlappedShardedExecutor,
                                   ShardedPipelineExecutor)
        kw = dict(slots=args.slots, max_len=MAX_LEN,
                  tree_capacity=pcfg.tree_buffer_capacity,
                  capacity=pcfg.capacity)
        if args.executor == "async":
            assert not args.paged, \
                "--executor async has no paged path yet (use --executor " \
                "sharded --paged)"
            executor = AsyncPipelineExecutor(
                target, draft, n_stages=args.stages,
                devices=None if mesh is None else pl.stage_devices(mesh),
                **kw)
        elif args.executor == "sharded":
            cls = OverlappedShardedExecutor if args.overlap \
                else ShardedPipelineExecutor
            executor = cls(target, draft, n_stages=len(jax.devices()),
                           mesh=mesh, paged=args.paged, page=args.page_size,
                           **kw)
        else:
            executor = LocalFusedExecutor(target, draft, paged=args.paged,
                                          page=args.page_size, **kw)
    engine = ServingEngine(
        target, draft, mode=args.mode, max_batch=args.slots,
        max_len=MAX_LEN, pipedec=pcfg, executor=executor)
    return executor, engine


def submit_requests(engine, args, vocab_size: int) -> None:
    """``--requests`` seeded random prompts of ``PROMPT_LEN`` tokens."""
    rng = np.random.default_rng(0)
    for uid in range(args.requests):
        prompt = rng.integers(0, vocab_size,
                              size=PROMPT_LEN).astype(np.int32)
        engine.submit(Request(uid, prompt, args.new_tokens))


def main(argv=None):
    """CLI entry: build target+draft bundles, pick the executor backend
    (``--executor local|sharded|async``), run the engine, print
    per-request results and DB stats.
    """
    enable_compile_cache()
    args = parse_args(argv)
    mesh = stage_mesh(args)
    target, draft = build_bundles(args, mesh)
    executor, engine = build_engine(args, target, draft, mesh)
    submit_requests(engine, args, target.cfg.vocab_size)
    results = engine.run()
    if args.executor == "async" and executor is not None:
        executor.shutdown()
    for uid, res in sorted(results.items()):
        extra = ""
        if res.stats is not None and hasattr(res.stats, "acceptance"):
            extra = (f" acc={res.stats.acceptance:.2f}"
                     f" tps={res.stats.tokens_per_timestep:.2f}")
        print(f"req {uid}: {res.tokens.tolist()[:10]}... "
              f"{res.latency_s*1e3:.1f}ms{extra}")


if __name__ == "__main__":
    main()
