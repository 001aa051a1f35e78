#!/usr/bin/env python3
"""Bring-up check: the Janus served path on a TPU at the paper's full width.

With no arguments it needs one chip. It drives the fleet path of
``repro.launch.serve`` with ``--execute`` — the paper's ViT-L@384 in bf16
(24 layers, d=1024, 16 heads, 577 tokens, seeded random weights) — through
the planner, the compiled device partitions, the LZW payload codec and the
bucketed, micro-batched cloud partitions, then checks what came out:

  (a) every served frame's logits against ``engine.split_inference`` (the
      unbatched, unpadded, op-by-op round trip) on the same bf16 weights,
      schedule, split and payload quantization;
  (b) at alpha=0, the bf16 unsplit forward against a float32 reference.

``--four-chips`` runs only the ``--mesh dp`` fleet path over four chips and
the same frames on one chip in the same process, and compares them.

  python chip_smoke.py               # one TPU chip
  python chip_smoke.py --four-chips  # a host with four

Readings go to earlier lines; when every check passes the last line is one
JSON object naming the device. The script exits non-zero, without that
line, when JAX finds no TPU, when it runs outside a checkout of this
repository, or when any check fails. It runs in one process and starts
none: a chip belongs to one process at a time.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "bench"))

from compile_counter import CompileCounter  # noqa: E402

# 4 streams x 3 frames on fixed 4G/static traces. On the paper's timing
# profile the planner picks alpha=0.27 at split 0 for most frames (ToMe
# merges inside the bucketed cloud program, which embeds the raw frames)
# and device-only for some (all 24 merge layers and the head in the
# device program).
ONE_CHIP_ARGV = ["--streams", "4", "--frames", "3", "--network", "4g",
                 "--mobility", "static", "--sla-ms", "150", "--seed", "0",
                 "--execute", "--bucket-edges", "2", "--telemetry-sample", "0"]
# The planner never picks an interior split on the paper's profile (its
# cloud is priced as a V100), so interior frames are set here and run
# through the engine's own execution calls: device partition, payload
# codec, then one padded cloud micro-batch of INTERIOR_FRAMES members.
INTERIOR_SPLIT = 12
INTERIOR_ALPHAS = (0.27, 0.25, 0.2, 0.15, 0.1)
INTERIOR_FRAMES = 2
# The --mesh dp path: on WiFi/static traces the planner sends every frame
# to the cloud at alpha=0, so all members of a micro-batch share one
# geometry, and batches of 4 (a long batch window fills each one) split
# evenly over the four chips. alpha=0 also keeps ToMe out of the
# comparison: in bf16 two compilations of the same merge layer can pick
# different near-tied merge pairs, a discrete change no tolerance covers.
FOUR_CHIP_ARGV = ["--streams", "8", "--frames", "2", "--network", "wifi",
                  "--mobility", "static", "--max-batch", "4",
                  "--batch-wait-ms", "10000", "--seed", "0", "--execute",
                  "--bucket-edges", "2", "--telemetry-sample", "0"]

# Tolerances on the relative L2 error ||x - ref|| / ||ref|| of the logits.
# (a) Both sides are the same bf16 model; they differ only in XLA fusion
#     (jitted programs may keep fused intermediates in f32, op-by-op eager
#     rounds every op), batching and bucket padding. Each moves a bf16 value
#     by a few ulps (2^-8 relative), so 2e-2 is about five ulps of the
#     logits' norm. A different ToMe merge or a wrong pad mask moves them
#     by far more.
TOL_SERVED_VS_SPLIT = 2e-2
# (b) bf16 keeps 8 significant bits (unit roundoff 2^-9 ~ 2e-3). 24 blocks
#     round the residual stream about 200 times; as a random walk that is
#     sqrt(200) * 2e-3 ~ 3e-2. Wrong math gives errors of order 1.
TOL_BF16_VS_F32 = 5e-2
# Four chips vs one: two compilations of the same bf16 model (sharded
# batch-4 vs unsharded batch-1 programs). Each may keep a different set of
# fused intermediates in f32, so they can differ by as much as either
# differs from float32, the bound of (b).
TOL_MESH_VS_ONE = TOL_BF16_VS_F32


class CheckFailed(Exception):
    pass


def check(ok: bool, what: str) -> None:
    print(f"[chip_smoke] check {'ok' if ok else 'FAILED'}: {what}")
    if not ok:
        raise CheckFailed(what)


def phase(counter: CompileCounter, name: str, snap: tuple) -> tuple:
    """Print the programs built since ``snap``: compiled apart from loaded
    from the persistent cache. Returns a new snapshot."""
    print(f"[chip_smoke] {name}: {counter.since(snap)}")
    return counter.snapshot()


def rel_err(x, ref) -> float:
    x = np.asarray(x, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.linalg.norm(x - ref) / max(np.linalg.norm(ref), 1e-30))


def build(argv):
    """The fleet runtime and model exactly as ``serve.main`` builds them."""
    from repro.launch import serve
    args = serve.build_parser().parse_args(argv)
    profile, eng_cfg, cfg, params, images = serve.setup(args)
    rt = serve.build_fleet(args, serve.spec_from_args(args), profile, eng_cfg,
                           cfg, params)
    return rt, cfg, images


def timed_run(rt, images, name: str):
    t0 = time.perf_counter()
    fs = rt.run(images=images)
    jax.block_until_ready([f.logits for f in fs.all_frames])
    wall = time.perf_counter() - t0
    decisions = sorted({(round(f.alpha, 2), f.split) for f in fs.all_frames})
    print(f"[chip_smoke] {name}: frames={len(fs.all_frames)} "
          f"batches={fs.batch_sizes} (alpha, split)={decisions} "
          f"wall_s={wall:.3f}")
    return fs


def check_logits(frames, n_classes: int, name: str) -> None:
    logits = [np.asarray(f.logits, np.float32) for f in frames]
    shapes = sorted({lg.shape for lg in logits})
    check(shapes == [(1, n_classes)] and all(np.isfinite(lg).all()
                                             for lg in logits),
          f"{name}: {len(logits)} frames' logits of shape {shapes}, finite")


def one_chip(counter: CompileCounter) -> None:
    from repro.core import engine, pruning
    from repro.core.scheduler import Decision
    from repro.models import vit as vit_lib

    snap = counter.snapshot()
    rt, cfg, images = build(ONE_CHIP_ARGV)
    eng, params = rt.engines[0], rt.params
    n, profile = cfg.n_layers, eng.profile
    check((n, cfg.num_tokens) == (profile.n_layers, profile.x0),
          f"executed model is the priced one ({n} layers, "
          f"{cfg.num_tokens} tokens, d={cfg.d_model}, {cfg.dtype.__name__})")
    snap = phase(counter, "setup", snap)

    # the served fleet path, cold (compiling) then warm
    runs = []
    for name in ("fleet cold", "fleet warm"):
        runs.append(timed_run(rt, images, name))
        snap = phase(counter, name, snap)
        check_logits(runs[-1].all_frames, cfg.n_classes, name)
    # (alpha, split, logits) of every served frame; the executed schedule
    # of a janus decision is the planner's for its alpha
    served = [(f.alpha, f.split, f.logits) for fs in runs
              for f in fs.all_frames]

    # interior split: device partition, codec, padded cloud micro-batch
    s = INTERIOR_SPLIT
    for alpha in INTERIOR_ALPHAS:
        sched = tuple(pruning.make_schedule(profile.schedule_kind, alpha, n,
                                            cfg.num_tokens))
        count = pruning.token_counts(cfg.num_tokens, sched)[s]
        if rt.buckets.edge_for(s, count) > count:
            break
    dec = Decision(alpha, s, 0.0, True, sched)
    t0 = time.perf_counter()
    plans, payloads = zip(*(eng.execute_device(dec, images)
                            for _ in range(INTERIOR_FRAMES)))
    engine.run_cloud_batch(rt.plan_cache, cfg, params, plans,
                           buckets=rt.buckets)
    jax.block_until_ready([p.logits for p in plans])
    print(f"[chip_smoke] interior: alpha={alpha} split={s} tokens={count} "
          f"bucket_edge={rt.buckets.edge_for(s, count)} "
          f"payload_bytes={payloads[0]} members={len(plans)} "
          f"wall_s={time.perf_counter() - t0:.3f}")
    snap = phase(counter, "interior", snap)
    check_logits(plans, cfg.n_classes, "interior")
    served += [(alpha, s, p.logits) for p in plans]
    pc = rt.plan_cache
    print(f"[chip_smoke] plan_cache traces={dict(pc.traces_by_kind)} "
          f"build_s={pc.build_s:.3f} hits={pc.hits} misses={pc.misses}")

    # (a) served logits vs the op-by-op split round trip
    refs, errs = {}, {}
    t0 = time.perf_counter()
    for alpha, split, logits in served:
        key = (round(alpha, 2), split)
        if key not in refs:
            sched = tuple(pruning.make_schedule(profile.schedule_kind, alpha,
                                                n, cfg.num_tokens))
            refs[key], _ = engine.split_inference(
                params, cfg, images, sched, split,
                quantize=eng.cfg.quantize_payload)
        errs[key] = max(errs.get(key, 0.0), rel_err(logits, refs[key]))
    worst = max(errs.values())
    print(f"[chip_smoke] (a) served vs split_inference: frames={len(served)} "
          f"max_rel_l2 per (alpha, split)="
          f"{ {k: f'{v:.3e}' for k, v in errs.items()} } "
          f"wall_s={time.perf_counter() - t0:.3f}")
    snap = phase(counter, "reference (a)", snap)
    check(worst <= TOL_SERVED_VS_SPLIT,
          f"(a) max rel L2 {worst:.3e} <= {TOL_SERVED_VS_SPLIT}")

    # (b) alpha=0 unsplit forward, bf16 vs float32 at highest precision
    fwd = jax.jit(vit_lib.forward_janus, static_argnums=(1, 3))
    no_merge = (0,) * n
    out = fwd(params, cfg, images, no_merge)
    cfg32 = dataclasses.replace(cfg, dtype=jnp.float32)
    params32 = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    with jax.default_matmul_precision("highest"):
        ref32 = fwd(params32, cfg32, images, no_merge)
    err = rel_err(out, ref32)
    print(f"[chip_smoke] (b) alpha=0 bf16 vs f32: rel_l2={err:.3e}")
    phase(counter, "reference (b)", snap)
    check(err <= TOL_BF16_VS_F32, f"(b) rel L2 {err:.3e} <= {TOL_BF16_VS_F32}")


def four_chips(counter: CompileCounter) -> None:
    snap = counter.snapshot()
    check(len(jax.devices()) == 4, f"four devices (found {len(jax.devices())})")
    rt_mesh, cfg, images = build(FOUR_CHIP_ARGV + ["--mesh", "dp"])
    fs_mesh = timed_run(rt_mesh, images, "fleet dp mesh")
    snap = phase(counter, "fleet dp mesh", snap)
    rules = rt_mesh.mesh_rules
    print(f"[chip_smoke] mesh={dict(rules.mesh.shape)} "
          f"fallbacks={rules.fallbacks}")
    check(not any(lg == "batch" for lg, _, _ in rules.fallbacks),
          "no batch axis dropped from the dp mesh")
    check(all(b % 4 == 0 for b in fs_mesh.batch_sizes),
          f"micro-batches divisible by 4: {fs_mesh.batch_sizes}")
    check_logits(fs_mesh.all_frames, cfg.n_classes, "dp mesh")

    rt_one, _, _ = build(FOUR_CHIP_ARGV)
    fs_one = timed_run(rt_one, images, "fleet one chip")
    phase(counter, "fleet one chip", snap)
    pairs = list(zip(fs_mesh.all_frames, fs_one.all_frames, strict=True))
    check(all((fm.alpha, fm.split) == (fo.alpha, fo.split)
              for fm, fo in pairs),
          "same decisions on the mesh and on one chip")
    worst = max(rel_err(fm.logits, fo.logits) for fm, fo in pairs)
    print(f"[chip_smoke] dp mesh vs one chip: frames={len(fs_mesh.all_frames)} "
          f"max_rel_l2={worst:.3e}")
    check(worst <= TOL_MESH_VS_ONE,
          f"mesh vs one chip max rel L2 {worst:.3e} <= {TOL_MESH_VS_ONE}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the --mesh dp fleet path over four chips "
                         "and the same frames on one chip")
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro" / "launch" / "serve.py").is_file():
        print(f"[chip_smoke] {ROOT} is not a checkout of this repository "
              "(src/repro is missing)", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"[chip_smoke] no TPU found: JAX's first device is "
              f"{devices[0].platform}; this check runs on a TPU only",
              file=sys.stderr)
        return 1
    from repro.launch import serve
    cache = serve.enable_compile_cache()
    counter = CompileCounter()
    print(f"[chip_smoke] device_kind={devices[0].device_kind} "
          f"count={len(devices)} jax={jax.__version__} compile_cache={cache}")
    t0 = time.perf_counter()
    try:
        (four_chips if args.four_chips else one_chip)(counter)
    except CheckFailed as e:
        print(f"[chip_smoke] FAILED: {e}", file=sys.stderr)
        return 1
    stats = devices[0].memory_stats() or {}
    print(f"[chip_smoke] peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
          f"programs={counter.since((0, 0.0, 0))} "
          f"total_wall_s={time.perf_counter() - t0:.3f}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
