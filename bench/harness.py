"""The benchmark's machinery: find a cell by name, build the served path,
time its ticks, trace a few, check the answers, and read the metrics.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric is a file of its own, found by the name that
``BENCHMARK.json`` gives it:

* ``bench/configs/<config file>``: the sizes, and ``family``, which names
  two modules: ``bench/configs/<family>.py``, the plain reference (it imports
  nothing of the program), with the seeded weights, ``merge_schedule``,
  ``num_tokens`` and ``frame_flops``; and ``bench/configs/<family>_served.py``,
  which holds every import of the program for the family: ``model_config``
  (the program's model config for the sizes) and ``param_specs`` (the layout
  the seeded weights are checked against);
* ``bench/traffic/<traffic>.json``: the traffic mix;
* ``bench/limits/<workload>.json``: the limit of each number compared;
* ``bench/metrics/<metric>.py``: a reader with ``read(run) -> float | None``.

Of the sizes the harness reads itself only ``family``, ``dtype``,
``image_size`` (the frames), ``num_layers``, ``num_classes`` and
``tome.schedule``; every sizes file carries them. The rest is the family's.

A tick is one ``FleetRuntime.run`` of the cell's fleet, one frame per
stream, timed from the call until every frame's logits are ready.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import statistics
import sys
import tempfile
import time
from collections import Counter

import numpy as np

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".jax_cache"
SPANS = ("tick", "rt.run", "block")
WARMUP_TICKS = 2
TRACE_MIN_TICKS, TRACE_MIN_S = 3, 1.0
CHECK_TICKS = 8


def log(msg: str) -> None:
    print(f"[bench] {msg}", flush=True)


# -- finding things by name ----------------------------------------------------

_MODULES: dict = {}


def load_module(path: pathlib.Path):
    if path in _MODULES:
        return _MODULES[path]
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}".replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    _MODULES[path] = mod
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list[dict]
    per_layer: list[dict]

    @property
    def family(self):
        """The plain reference of the configuration's family."""
        return load_module(BENCH / "configs" / f"{self.config['family']}.py")

    @property
    def served(self):
        """The family's adapter to the program's model."""
        return load_module(BENCH / "configs" / f"{self.config['family']}_served.py")


def load_cell(name: str, bench: dict | None = None) -> Cell:
    if bench is None:
        bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]

    def reports(m):
        return name in m["workloads"] if "workloads" in m else True

    e2e = [m for m in bench["end_to_end"] if reports(m)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m
                     else m["moves"] in e2e_names)]
    return Cell(
        name=name, chips=w["chips"],
        config=json.loads((ROOT / conf["file"]).read_text()),
        traffic=json.loads((BENCH / "traffic" / f"{w['traffic']}.json").read_text()),
        limits=json.loads((BENCH / "limits" / f"{name}.json").read_text()),
        end_to_end=e2e, per_layer=per_layer)


def reader(metric: str):
    return load_module(BENCH / "metrics" / f"{metric}.py")


# -- the served path -------------------------------------------------------------

def seed_keys(seed: int, n: int):
    """``n`` JAX keys from a seed of any size (the spawned words are 31-bit,
    which every JAX key accepts)."""
    import jax
    words = np.random.SeedSequence(seed).generate_state(n, dtype=np.uint32)
    return [jax.random.key(int(w >> 1)) for w in words]


def make_frames(key, res: int, spec: dict) -> tuple:
    """``spec["count"]`` camera frames [1, res, res, 3] in one program: a
    random field of one value per ``spec["cell_px"]`` pixels, bilinearly
    upsampled, plus pixel noise of ``spec["noise"]``. Like a camera's, the
    frames are smooth with fine detail, so neighbouring patches are alike
    and ToMe's near-tied merges pair tokens that are alike too."""
    import jax
    import jax.numpy as jnp
    coarse = res // spec["cell_px"]

    def one(k):
        k1, k2 = jax.random.split(k)
        field = jax.random.normal(k1, (1, coarse, coarse, 3), jnp.float32)
        return (jax.image.resize(field, (1, res, res, 3), "bilinear")
                + spec["noise"] * jax.random.normal(k2, (1, res, res, 3), jnp.float32))

    return jax.jit(lambda k: tuple(one(kk) for kk in jax.random.split(k, spec["count"])))(key)


@dataclasses.dataclass
class Served:
    rt: object            # repro.serving.fleet.FleetRuntime
    params: dict
    images: tuple         # each [1, H, W, 3]
    max_batch: int


def build(cell: Cell, seed: int) -> Served:
    """Weights and frames from the seed, then the fleet runtime as the
    program's CLI composes it (``serve.make_profile``, ``EngineConfig``,
    ``workload.build_runtime``), for this cell's configuration."""
    import jax
    import jax.numpy as jnp
    from repro.core import bucketing, engine
    from repro.launch import serve
    from repro.models import param as param_lib
    from repro.serving import workload

    c, t = cell.config, cell.traffic
    dtype = jnp.dtype(c["dtype"])
    model_cfg = cell.served.model_config(c, dtype)
    wkey, ikey = seed_keys(seed, 2)
    params = cell.family.init_params(c, wkey, dtype)
    want = param_lib.abstract_params(cell.served.param_specs(model_cfg), dtype)
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), params)
    if jax.tree.structure(got) != jax.tree.structure(want) or got != want:
        raise ValueError("the seeded weights do not match the served model's layout")
    images = make_frames(ikey, c["image_size"], t["frames"])

    profile = serve.make_profile(model_cfg)
    if profile.schedule_kind != c["tome"]["schedule"]:
        raise ValueError(f"the planner prunes on a {profile.schedule_kind} "
                         f"schedule, the configuration states {c['tome']['schedule']}")
    spec = workload.WorkloadSpec(
        n_streams=t["streams"], n_frames=1, policy=t["policy"],
        sla_ms=t["sla_ms"], seed=t["trace_set"],
        network=workload.NetworkConfig(network=t["network"],
                                       mobility=t["mobility"]),
        max_batch=t["max_batch"], max_wait_ms=t["batch_wait_ms"])
    # the simulated clock leaves out the planner's measured wall time: billed
    # in, it moves frames between micro-batches from tick to tick, and every
    # new batch size is a new program
    eng_cfg = engine.EngineConfig(sla_s=t["sla_ms"] / 1e3, execute=True,
                                  include_scheduler_overhead=False)
    buckets = bucketing.BucketingConfig(n_edges=t["bucket_edges"]) \
        if t["bucket_edges"] else None
    rt = workload.build_runtime(spec, profile, eng_cfg, model_cfg=model_cfg,
                                params=params, bucketing=buckets)
    return Served(rt, params, images, rt.cloud.max_batch)


@dataclasses.dataclass
class Tick:
    host_s: float     # until FleetRuntime.run returned
    total_s: float    # until every frame's logits were ready


def tick(rt, image):
    import jax
    with jax.profiler.TraceAnnotation("tick"):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("rt.run"):
            fs = rt.run(images=image)
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("block"):
            jax.block_until_ready([f.logits for f in fs.all_frames
                                   if f.logits is not None])
        t2 = time.perf_counter()
    return Tick(t1 - t0, t2 - t0), fs


def served_frames(fs, image_index: int) -> list[tuple]:
    """(image index, alpha, split, logits) of every frame of a tick."""
    return [(image_index, f.alpha, f.split, f.logits) for f in fs.all_frames]


def tick_flops(cell: Cell, fs) -> float:
    c, fam = cell.config, cell.family
    n_tok = fam.num_tokens(c)
    return sum(fam.frame_flops(c, fam.merge_schedule(f.alpha, c["num_layers"], n_tok))
               for f in fs.all_frames)


# -- the check -----------------------------------------------------------------------

def compare(cell: Cell, params, images, frames, against=None) -> dict:
    """Numbers that compare served frames with the plain reference.

    ``frames``: (image index, alpha, split, logits) tuples. The reference
    runs once per (alpha, split) over all the cell's frames. ``against``
    puts other logits in the served ones' place: a callable
    (alpha, split) -> logits per image, for the control."""
    import jax.numpy as jnp
    fam, c = cell.family, cell.config
    stack = jnp.concatenate(images, axis=0)
    refs, alts = {}, {}
    missing = nonfinite = 0
    worst_rel = 0.0
    for idx, alpha, split, logits in frames:
        key = (alpha, split)
        if key not in refs:
            refs[key] = np.asarray(fam.logits(params, c, stack, alpha, split), np.float64)
            if against is not None:
                alts[key] = np.asarray(against(alpha, split), np.float64)
        ref = refs[key][idx]
        if against is not None:
            got = alts[key][idx]
        elif logits is None or logits.shape != (1, c["num_classes"]):
            missing += 1
            continue
        else:
            got = np.asarray(logits, np.float64)[0]
        if not np.all(np.isfinite(got)):
            nonfinite += 1
            continue
        worst_rel = max(worst_rel, float(np.linalg.norm(got - ref) / np.linalg.norm(ref)))
    return {"missing": missing, "nonfinite": nonfinite,
            "rel_l2": worst_rel}


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in numbers}
    return all(v["value"] <= v["limit"] for v in checks.values()), checks


# -- the window -------------------------------------------------------------------

@dataclasses.dataclass
class Window:
    ticks: list[Tick]
    frames: int
    failed: int            # frames whose logits never came
    seconds: float
    programs: int          # partition programs looked up and called
    batch_sizes: list[int]
    kept: list[list[tuple]]   # the ticks drawn for the check

    def checked(self) -> list[tuple]:
        """The drawn ticks' frames, logits copied to the host."""
        return [(i, a, s, None if lg is None else np.asarray(lg))
                for frames in self.kept for i, a, s, lg in frames]


def measure(rt, images, seconds: float, seed: int) -> Window:
    """Ticks back to back, one outstanding, to the first tick boundary after
    ``seconds``. ``CHECK_TICKS`` of them, drawn from the seed (reservoir
    sampling), keep their frames for the check."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
    pc = rt.plan_cache
    lookups0 = pc.hits + pc.misses
    kept, ticks, batch_sizes, failed = [], [], [], 0
    t0 = time.perf_counter()
    while True:
        i = len(ticks)
        tk, fs = tick(rt, images[i % len(images)])
        ticks.append(tk)
        batch_sizes += fs.batch_sizes
        failed += sum(f.logits is None for f in fs.all_frames)
        frames = served_frames(fs, i % len(images))
        if i < CHECK_TICKS:
            kept.append(frames)
        else:
            j = int(rng.integers(0, i + 1))
            if j < CHECK_TICKS:
                kept[j] = frames
        if time.perf_counter() - t0 >= seconds:
            break
    return Window(ticks, len(ticks) * len(rt.streams), failed,
                  time.perf_counter() - t0, pc.hits + pc.misses - lookups0,
                  batch_sizes, kept)


# -- one run ---------------------------------------------------------------------------

@dataclasses.dataclass
class Run:
    """What the metric readers read."""
    ticks: list[Tick]
    frames: int
    failed: int
    window_s: float
    programs: int
    batch_sizes: list[int]
    max_batch: int
    chips: int
    peak_flops: float
    trace: object = None          # trace.Reduction of the traced ticks
    trace_flops: float = 0.0


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool,
             t_start: float, counter, device_kind: str, chips: int) -> dict:
    import jax
    import flops
    import trace as trace_lib

    peak_flops = flops.peak(device_kind)
    snap = counter.snapshot()
    sv = build(cell, seed)
    rt, images = sv.rt, sv.images
    for i in range(WARMUP_TICKS):
        _, fs = tick(rt, images[i % len(images)])
    decisions = Counter((round(f.alpha, 2), f.split) for f in fs.all_frames)
    log(f"set-up: {counter.since(snap)}; decisions (alpha, split) x frames: "
        f"{dict(sorted(decisions.items()))}; cloud micro-batches: {fs.batch_sizes}; "
        f"max_batch={sv.max_batch}")

    snap = counter.snapshot()
    t0 = time.perf_counter()
    setup_s = t0 - t_start
    win = measure(rt, images, seconds, seed)
    in_window = counter.since(snap)
    ticks, n_frames = win.ticks, win.frames
    run = Run(ticks=ticks, frames=n_frames, failed=win.failed,
              window_s=win.seconds, programs=win.programs,
              batch_sizes=win.batch_sizes, max_batch=sv.max_batch, chips=chips,
              peak_flops=peak_flops)
    lat = sorted(t.total_s for t in ticks)
    q = statistics.quantiles(lat, n=20) if len(lat) > 1 else [lat[0]] * 19
    log(f"window: ticks={len(ticks)} frames={n_frames} "
        f"window_s={win.seconds:.6f} tick_p50_ms={1e3 * statistics.median(lat):.4f} "
        f"tick_p95_ms={1e3 * q[18]:.4f} in-window {in_window}")
    if in_window["programs"]:
        log(f"WARNING: {in_window['programs']} programs built inside the window")

    if traced:
        with tempfile.TemporaryDirectory() as d:
            jax.profiler.start_trace(d)
            traced_ticks, t_tr = [], time.perf_counter()
            while len(traced_ticks) < TRACE_MIN_TICKS or \
                    time.perf_counter() - t_tr < TRACE_MIN_S:
                traced_ticks.append(tick(rt, images[len(traced_ticks) % len(images)])[1])
            jax.profiler.stop_trace()
            n = len(traced_ticks)
            run.trace_flops = sum(tick_flops(cell, fs) for fs in traced_ticks)
            del traced_ticks
            run.trace = trace_lib.reduce(trace_lib.load(d, SPANS), "tick")
        log(f"trace: ticks={n} window_s={run.trace.window_s:.6f} "
            f"busy_s={run.trace.busy_s:.6f} devices={run.trace.n_devices}")

    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in jax.local_devices())
    log(f"peak device memory {mem} bytes")

    # free the served path, then check the kept ticks against the reference
    checked = win.checked()
    params, images = sv.params, sv.images
    del sv, rt, fs, win
    jax.clear_caches()
    snap, t_ref = counter.snapshot(), time.perf_counter()
    numbers = compare(cell, params, images, checked)
    ok, checks = judge(numbers, cell.limits)
    log(f"check: {len(checked)} frames of {min(CHECK_TICKS, len(ticks))} ticks "
        f"in {time.perf_counter() - t_ref:.3f} s, programs {counter.since(snap)}")

    e2e = {"frames_per_s": (n_frames - run.failed) / run.window_s,
           "tick_p95_ms": 1e3 * q[18],
           "setup_s": setup_s}
    if traced:
        metrics = {}
        for m in cell.per_layer:
            v = reader(m["name"]).read(run)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem}
    out = {"correct": ok, "attempted": n_frames, "failed": run.failed,
           "metrics": metrics, "device": device}
    if traced:
        device.update(busy_s=run.trace.busy_s, window_s=run.trace.window_s)
        out["breakdown"] = {"device_ops": [list(x) for x in run.trace.device_ops],
                            "idle_gaps": [list(x) for x in run.trace.idle_gaps]}
    out["checks"] = checks
    return out


def enable_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, every program in it however fast it compiled, and no size
    cap: a cap set in the environment (``JAX_COMPILATION_CACHE_MAX_SIZE``)
    below the cell's programs evicts them as they are written, and every
    run compiles anew."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)


def main(argv, t_start: float) -> int:
    import argparse
    ap = argparse.ArgumentParser(description="One run of one benchmark cell on the chip.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not (ROOT / "src" / "repro" / "serving" / "fleet.py").is_file():
        print(f"[bench] {ROOT} holds no program (src/repro is missing)", file=sys.stderr)
        return 2
    cell = load_cell(args.workload)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"[bench] no TPU: JAX's first device is {devices[0].platform}; "
              "this benchmark measures the chip only", file=sys.stderr)
        return 1
    if len(devices) < cell.chips:
        print(f"[bench] {cell.name} needs {cell.chips} chips, JAX finds {len(devices)}",
              file=sys.stderr)
        return 1
    enable_compile_cache()
    from compile_counter import CompileCounter
    counter = CompileCounter()
    log(f"device_kind={devices[0].device_kind} count={len(devices)} "
        f"jax={jax.__version__} cell={cell.name} seed={args.seed} "
        f"seconds={args.seconds} trace={args.trace} compile cache "
        f"{jax.config.jax_compilation_cache_dir} "
        f"({sum(1 for _ in CACHE_DIR.glob('*-cache'))} entries)")
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), t_start,
                   counter, devices[0].device_kind, cell.chips)
    log(f"process compiles {counter.since((0, 0.0, 0))}")
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0
