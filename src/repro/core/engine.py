"""Janus §IV: execution engine — Jdevice / Jcloud runtime.

Simulates the two-tier deployment end-to-end over a network trace:

  per frame:  estimate bandwidth (harmonic mean of past observations)
              -> dynamic scheduler picks (α, split)
              -> device partition runs layers [0, s) (with the mixed pruning
                 schedule), LZW-compresses the pruned intermediate
              -> transfer at the *actual* trace bandwidth
              -> cloud partition runs layers [s, N) + head

Decision + accounting hot path is table-driven: every engine for a given
``ModelProfile`` shares one precomputed ``planner.PlannerTables`` (α grid,
schedules, token-count matrix, latency prefix sums), so the per-frame
scheduler call is vectorized array math and ``account_breakdown`` is two
numpy reductions instead of pure-Python per-layer sums. The fixed baseline
schedule/counts (Device/Cloud/Mixed policies) are derived once per engine,
not per frame.

The *math* path (``execute=True``) really runs both partitions — split
inference is verified elsewhere to equal the monolithic forward — while the
*latency* path accounts device/cloud compute via the fitted linear profilers
(exactly the quantities the paper's scheduler reasons about) plus the measured
payload size over the trace bandwidth. ``execute=False`` skips the math for
long trace sweeps (benchmarks) and uses the schedule-derived payload size.
Partition programs are ``jax.jit``-compiled once per (schedule, split, batch)
geometry and cached in a ``CompiledPlanCache`` — repeat frames with the same
decision reuse the compiled executable instead of retracing, and the fleet
runtime batches same-geometry cloud partitions from a micro-batch into one
stacked forward (``run_cloud_batch``).

Baselines (§V-B): Device-Only / Cloud-Only / Mixed (NeuroSurgeon degenerates to
Mixed for ViTs), each with ToMe's maximum fixed pruning level.

Fault story: a blocked network (bandwidth ~ 0) drives the scheduler to the
device-only split — Janus's scheduler *is* the failover path for network
partitions (DESIGN.md §4).

The per-frame step (``plan_frame``: decide -> account; ``frame_result``:
stamp + SLA check; caller observes the true bandwidth) is factored out of
``run_trace`` so the single-stream loop here and the multi-stream fleet
runtime (``repro.serving.fleet``) share one code path; the fleet additionally
needs ``account_breakdown``'s device/comm/cloud phase split to place cloud
work on a shared, finite tier, and passes ``defer_cloud=True`` so pending
cloud partitions execute batched at micro-batch dispatch time.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import compression, planner, pruning, scheduler as sched_lib
from repro.core.bucketing import BucketTable
from repro.core.bandwidth import HarmonicMeanEstimator, NetworkTrace
from repro.core.pruning import AccuracyModel
from repro.core.scheduler import Decision, ModelProfile
from repro.models import vit as vit_lib
from repro.sharding import rules as rules_lib


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    sla_s: float
    t: float = 0.01
    k: int = 5
    quantize_payload: bool = True
    execute: bool = False
    baseline_fixed_r: int = 23  # ToMe max fixed pruning (ViT-L@384; §V-B)
    include_scheduler_overhead: bool = True  # bill Algorithm-1 wall time
    planner: str = "tables"  # "tables" (vectorized) | "legacy" (reference loop)
    # capture-quality multiplier on the accuracy term (a phone-class camera
    # degrades accuracy, not just latency; see workload.DeviceTier) — 1.0 is
    # the identity, so default configs reproduce the unscaled model bit-exact
    accuracy_scale: float = 1.0
    # Algorithm-1 knobs as one value object; when set it overrides the flat
    # ``t``/``k`` fields above (which are the deprecated pre-PlannerConfig
    # shape, kept for one release)
    planner_cfg: planner.PlannerConfig | None = None

    def __post_init__(self):
        if self.accuracy_scale <= 0:
            raise ValueError(
                f"accuracy_scale must be > 0, got {self.accuracy_scale}")

    @property
    def planner_config(self) -> planner.PlannerConfig:
        """Resolved planner knobs: ``planner_cfg`` when set, else the flat
        ``t``/``k`` fields."""
        if self.planner_cfg is not None:
            return self.planner_cfg
        return planner.PlannerConfig(t=self.t, k=self.k)


@dataclasses.dataclass(frozen=True)
class LatencyBreakdown:
    """Per-frame latency split into the three serving phases. The fleet
    runtime needs the phases separately: device+comm run on the client's own
    hardware/link, while ``cloud_s`` occupies the shared cloud tier."""
    device_s: float
    comm_s: float
    cloud_s: float

    @property
    def total_s(self) -> float:
        return self.device_s + self.comm_s + self.cloud_s


@dataclasses.dataclass
class FrameResult:
    latency_s: float
    violated: bool
    deviation: float
    alpha: float
    split: int
    accuracy: float
    payload_bytes: float
    bandwidth_bps: float
    queue_s: float = 0.0  # extra delay beyond the standalone frame latency
    # (shared-cloud queueing + batch inflation; 0 for the single-stream engine)
    logits: Any = None    # real-math output when execute=True (else None)


@dataclasses.dataclass
class ExecPlan:
    """Pending real-math execution state for one frame (execute=True).

    The device partition runs at plan time; ``x``/``sizes`` hold the
    post-wire activation entering the cloud partition. ``logits`` is filled
    either inline (single-stream / device-only) or by ``run_cloud_batch``
    when the fleet dispatches the frame's micro-batch."""
    schedule: tuple[int, ...]   # exec-geometry merge schedule
    split: int                  # exec-geometry split (0..n_exec+1)
    x: Any = None
    sizes: Any = None
    logits: Any = None


@dataclasses.dataclass(frozen=True)
class FrameStep:
    """One planned/accounted frame: the output of ``decide -> account`` before
    it is stamped into a ``FrameResult`` (which may add queueing delay)."""
    decision: Decision
    breakdown: LatencyBreakdown
    payload_bytes: float
    bandwidth_bps: float
    accuracy: float
    exec_plan: ExecPlan | None = None


@dataclasses.dataclass
class RunStats:
    """Frame-level statistics. Every statistic is total on the empty frame
    list (0.0, not a crash/NaN): an all-dropped open-loop stream legitimately
    completes zero frames and still gets aggregated by the fleet runtime."""
    frames: list[FrameResult]

    def _mean(self, values: list[float]) -> float:
        return float(np.mean(values)) if values else 0.0

    @property
    def violation_ratio(self) -> float:
        return self._mean([f.violated for f in self.frames])

    @property
    def avg_throughput_fps(self) -> float:
        if not self.frames:
            return 0.0
        total = sum(f.latency_s for f in self.frames)
        return len(self.frames) / total if total > 0 else float("inf")

    @property
    def avg_latency_s(self) -> float:
        return self._mean([f.latency_s for f in self.frames])

    @property
    def p50_latency_s(self) -> float:
        if not self.frames:
            return 0.0
        return float(np.percentile([f.latency_s for f in self.frames], 50))

    @property
    def p99_latency_s(self) -> float:
        if not self.frames:
            return 0.0
        return float(np.percentile([f.latency_s for f in self.frames], 99))

    @property
    def avg_accuracy(self) -> float:
        return self._mean([f.accuracy for f in self.frames])

    @property
    def avg_deviation(self) -> float:
        return self._mean([f.deviation for f in self.frames])

    @property
    def avg_queue_s(self) -> float:
        return self._mean([f.queue_s for f in self.frames])


# ---------------------------------------------------------------------------
# split execution (the real math path)
# ---------------------------------------------------------------------------


def device_forward(params: dict, cfg: vit_lib.ViTConfig, images: jax.Array,
                   schedule: Sequence[int], split: int, scores_fn=None):
    """Jdevice: embed + layers [0, split). Returns (x, sizes)."""
    x = vit_lib.embed_tokens(params, cfg, images)
    sizes = vit_lib.initial_sizes(x)
    return vit_lib.run_blocks(params, cfg, x, sizes, schedule, 0, split, scores_fn=scores_fn)


def cloud_forward(params: dict, cfg: vit_lib.ViTConfig, x: jax.Array, sizes: jax.Array,
                  schedule: Sequence[int], split: int, scores_fn=None) -> jax.Array:
    """Jcloud: layers [split, N) + head."""
    x, _ = vit_lib.run_blocks(params, cfg, x, sizes, schedule, split, cfg.n_layers,
                              scores_fn=scores_fn)
    return vit_lib.head_apply(params, cfg, x)


_device_program = jax.jit(device_forward, static_argnums=(1, 3, 4, 5))
_cloud_program = jax.jit(cloud_forward, static_argnums=(1, 4, 5, 6))


def split_inference(params: dict, cfg: vit_lib.ViTConfig, images: jax.Array,
                    schedule: Sequence[int], split: int, *,
                    quantize: bool = False, scores_fn=None):
    """Full Jdevice->wire->Jcloud round trip, unbatched and unpadded: each
    partition is one jitted program and the codec runs on the host between
    them. Returns (logits, payload|None).

    Jitted, not op by op: in bf16 an op-by-op run rounds every intermediate
    that a fused program keeps wider, and those ulps are enough to flip a
    near-tied ToMe merge — a different, equally valid result."""
    n = cfg.n_layers
    split = min(max(split, 0), n + 1)
    s = n if split == n + 1 else split
    schedule = tuple(int(r) for r in schedule)
    x, sizes = _device_program(params, cfg, images, schedule, s, scores_fn)
    payload = None
    if split not in (0, n + 1):
        payload = compression.activation_payload(x, quantize=quantize)
        x = jnp.asarray(compression.decode_activation(payload), dtype=cfg.dtype)
    logits = _cloud_program(params, cfg, x, sizes, schedule, s, scores_fn)
    return logits, payload


class CompiledPlanCache:
    """``jax.jit`` executables for device/cloud partition programs, keyed by
    (partition, model config, schedule, split, input geometry).

    Without this every executed frame rebuilds and retraces the unrolled
    partition program even when the scheduler re-picks the same (α, split).
    ``hits``/``misses`` count cache lookups; ``traces`` counts actual jax
    traces (the wrapped fn bumps it only while tracing), so tests can assert
    "second frame with the same geometry does not retrace";
    ``traces_by_kind`` splits the same counter per partition program so the
    execute bench can bound *cloud* retraces by the bucket-table cell count.

    ``rules`` (optional ``sharding.Rules``) makes every compiled partition
    mesh-aware: the partition programs trace under ``use_rules``, so the
    ``constrain`` annotations inside ``vit.run_blocks`` /
    ``run_blocks_padded`` become real ``NamedSharding`` constraints —
    data-parallel over the stacked fleet batch, tensor-parallel over
    heads/MLP when the rules profile maps them. With ``rules=None`` (the
    default, and any single-device mesh) the programs are unchanged.
    """

    def __init__(self, rules=None):
        self._fns: dict[tuple, Callable] = {}
        self.rules = rules
        self.hits = 0
        self.misses = 0
        self.traces = 0
        self.traces_by_kind: dict[str, int] = {}

    def _bump(self, kind: str) -> None:
        self.traces += 1
        self.traces_by_kind[kind] = self.traces_by_kind.get(kind, 0) + 1

    def _get(self, key: tuple, build: Callable[[], Callable]) -> Callable:
        fn = self._fns.get(key)
        if fn is None:
            self.misses += 1
            fn = self._fns[key] = build()
        else:
            self.hits += 1
        return fn

    @staticmethod
    def _shape_key(arr) -> tuple:
        return (tuple(arr.shape), str(arr.dtype))

    def device_fn(self, cfg: vit_lib.ViTConfig, schedule: tuple[int, ...],
                  split: int, images) -> Callable:
        key = ("device", cfg, schedule, split, self._shape_key(images))

        def build():
            def traced(params, images):
                self._bump("device")
                with rules_lib.use_rules(self.rules):
                    return device_forward(params, cfg, images, schedule, split)
            return jax.jit(traced)

        return self._get(key, build)

    def cloud_fn(self, cfg: vit_lib.ViTConfig, schedule: tuple[int, ...],
                 split: int, x) -> Callable:
        key = ("cloud", cfg, schedule, split, self._shape_key(x))

        def build():
            def traced(params, x, sizes):
                self._bump("cloud")
                with rules_lib.use_rules(self.rules):
                    return cloud_forward(params, cfg, x, sizes, schedule, split)
            return jax.jit(traced)

        return self._get(key, build)

    def cloud_padded_fn(self, cfg: vit_lib.ViTConfig, suffix: tuple[int, ...],
                        split: int, x) -> Callable:
        """Bucketed cloud partition: same program for every plan that shares
        (schedule suffix past the split, split, bucket edge) — the key holds
        only the suffix, since layers [0, split) never run here."""
        key = ("cloud_padded", cfg, suffix, split, self._shape_key(x))

        def build():
            schedule = (0,) * split + tuple(suffix)

            def traced(params, x, sizes):
                self._bump("cloud_padded")
                with rules_lib.use_rules(self.rules):
                    x2, _ = vit_lib.run_blocks_padded(
                        params, cfg, x, sizes, schedule, split, cfg.n_layers)
                    return vit_lib.head_apply(params, cfg, x2)
            return jax.jit(traced)

        return self._get(key, build)


def _pad_tokens(x: jax.Array, sizes: jax.Array, edge: int):
    """Pad the token dim up to ``edge`` with zero-value, zero-size tokens.
    Size 0 is the whole masking contract: ``log(0) = -inf`` proportional-
    attention bias excludes pads from every softmax exactly, and the
    pad-aware merge keys off ``sizes <= 0``."""
    pad = edge - x.shape[1]
    if pad == 0:
        return x, sizes
    return (jnp.pad(x, ((0, 0), (0, pad), (0, 0))),
            jnp.pad(sizes, ((0, 0), (0, pad))))


def run_cloud_batch(cache: CompiledPlanCache, cfg: vit_lib.ViTConfig,
                    params: dict, plans: Sequence[ExecPlan],
                    buckets: BucketTable | None = None) -> None:
    """Execute pending cloud partitions, batching same-geometry plans into one
    stacked forward (micro-batched fleet items usually share the decision, so
    this turns B serial forwards into one [B·b, tokens, d] call). Fills each
    plan's ``logits`` in place.

    Without ``buckets``, plans batch only when their full (schedule, split,
    token-count) geometry matches. With a ``BucketTable``, plans that share
    just the *schedule suffix past the split* are padded up to a common
    bucket edge and batch together — mixed-α traffic at a shared split
    collapses onto a handful of compiled geometries (``cloud_padded_fn``),
    and retraces are bounded by the table's (split, edge) cell count instead
    of the number of distinct α in flight.
    """
    n = cfg.n_layers
    groups: dict[tuple, list[ExecPlan]] = {}
    for plan in plans:
        if plan is None or plan.logits is not None:
            continue
        s = n if plan.split == n + 1 else plan.split
        if buckets is None:
            key = (plan.schedule, s, tuple(plan.x.shape[1:]), str(plan.x.dtype))
        else:
            edge = buckets.edge_for(s, plan.x.shape[1])
            key = (plan.schedule[s:], s, edge, plan.x.shape[2],
                   str(plan.x.dtype))
        groups.setdefault(key, []).append(plan)
    for key, members in groups.items():
        if buckets is None:
            schedule, s = key[0], key[1]
            x = jnp.concatenate([m.x for m in members], axis=0)
            sizes = jnp.concatenate([m.sizes for m in members], axis=0)
            fn = cache.cloud_fn(cfg, schedule, s, x)
        else:
            suffix, s, edge = key[0], key[1], key[2]
            # pad once per distinct token count, not once per member: the
            # eager pad/concat dispatches then scale with the handful of
            # distinct counts in flight instead of the fleet size
            by_count: dict[int, list[ExecPlan]] = {}
            for m in members:
                by_count.setdefault(m.x.shape[1], []).append(m)
            chunks, members = [], []
            for t in sorted(by_count):
                ms = by_count[t]
                cx = ms[0].x if len(ms) == 1 else \
                    jnp.concatenate([m.x for m in ms], axis=0)
                cs = ms[0].sizes if len(ms) == 1 else \
                    jnp.concatenate([m.sizes for m in ms], axis=0)
                chunks.append(_pad_tokens(cx, cs, edge))
                members.extend(ms)
            x = chunks[0][0] if len(chunks) == 1 else \
                jnp.concatenate([c[0] for c in chunks], axis=0)
            sizes = chunks[0][1] if len(chunks) == 1 else \
                jnp.concatenate([c[1] for c in chunks], axis=0)
            fn = cache.cloud_padded_fn(cfg, suffix, s, x)
        logits = fn(params, x, sizes)
        off = 0
        for m in members:
            b = m.x.shape[0]
            m.logits = logits[off:off + b]
            off += b


def shard_params(params: dict, cfg: vit_lib.ViTConfig, rules) -> dict:
    """Place a param tree per the rules' mesh before serving (dp replicates,
    tp shards heads/MLP/vocab). The cache's compiled programs then consume
    already-resident shards instead of re-transferring per call."""
    shardings = rules_lib.params_sharding(vit_lib.specs(cfg), rules)
    return jax.device_put(params, shardings)


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------


class JanusEngine:
    def __init__(self, profile: ModelProfile, engine_cfg: EngineConfig,
                 acc_model: AccuracyModel | None = None,
                 model_cfg: vit_lib.ViTConfig | None = None,
                 params: dict | None = None,
                 plan_cache: CompiledPlanCache | None = None):
        self.profile = profile
        self.cfg = engine_cfg
        self.acc = acc_model or AccuracyModel()
        self.model_cfg = model_cfg
        self.params = params
        self._estimator = HarmonicMeanEstimator()
        # shared vectorized planner state (one tables instance per profile
        # value — fleet engines sharing a profile share the tables)
        self.tables = planner.tables_for(profile, engine_cfg.planner_config)
        self.plan_cache = plan_cache or CompiledPlanCache()
        # fixed baseline schedule/counts: derived once, not per frame
        self._fixed_schedule = tuple(pruning.clamp_schedule(
            pruning.fixed_schedule(engine_cfg.baseline_fixed_r, profile.n_layers),
            profile.x0))
        self._fixed_counts = np.asarray(
            pruning.token_counts(profile.x0, self._fixed_schedule), dtype=np.int64)
        self._counts_memo: dict[tuple[int, ...], np.ndarray] = {
            self._fixed_schedule: self._fixed_counts}

    # -- latency accounting -------------------------------------------------
    def _counts_for(self, schedule: tuple[int, ...]) -> np.ndarray:
        """Token counts for a decision's schedule (memoized — Algorithm 1
        revisits a handful of schedules across a trace)."""
        counts = self._counts_memo.get(schedule)
        if counts is None:
            counts = self._counts_memo[schedule] = np.asarray(
                pruning.token_counts(self.profile.x0, schedule), dtype=np.int64)
        return counts

    def account_breakdown(self, counts: Sequence[int], split: int,
                          payload_bytes: float, bandwidth_bps: float,
                          rtt_s: float) -> LatencyBreakdown:
        """Phase-separated latency for one frame at the given split
        (vectorized over layers via the linear profilers)."""
        p = self.profile
        n = p.n_layers
        counts = np.asarray(counts, dtype=np.float64)
        if split == 0:
            comm = p.raw_input_bytes * 8 / bandwidth_bps + rtt_s
            cloud = p.cloud_embed_s + float(p.cloud.predict(counts[:n]).sum()) + p.head_s
            return LatencyBreakdown(0.0, comm, cloud)
        if split == n + 1:
            dev = p.device_embed_s + float(p.device.predict(counts[:n]).sum()) + p.head_s
            return LatencyBreakdown(dev, 0.0, 0.0)
        dev = p.device_embed_s + float(p.device.predict(counts[:split]).sum())
        comm = payload_bytes * 8 / bandwidth_bps + rtt_s
        cloud = float(p.cloud.predict(counts[split:n]).sum()) + p.head_s
        return LatencyBreakdown(dev, comm, cloud)

    def _account(self, counts: Sequence[int], split: int, payload_bytes: float,
                 bandwidth_bps: float, rtt_s: float) -> float:
        return self.account_breakdown(counts, split, payload_bytes,
                                      bandwidth_bps, rtt_s).total_s

    def _payload_bytes(self, counts: Sequence[int], split: int) -> float:
        if split in (0, self.profile.n_layers + 1):
            return 0.0
        return float(counts[split]) * self.profile.token_bytes

    def _decide(self, policy: str, bandwidth_est: float, rtt_s: float) -> Decision:
        p, c = self.profile, self.cfg
        n = p.n_layers
        if policy == "janus":
            if c.planner == "legacy":
                pc = c.planner_config
                return sched_lib._reference_schedule(p, bandwidth_est, rtt_s,
                                                     c.sla_s, t=pc.t, k=pc.k,
                                                     alpha_grid=pc.alpha_grid)
            return self.tables.decide(bandwidth_est, rtt_s, c.sla_s)
        fixed, counts = self._fixed_schedule, self._fixed_counts
        if policy == "device":
            return Decision(0.0, n + 1, self._account(counts, n + 1, 0, bandwidth_est, rtt_s),
                            True, fixed)
        if policy == "cloud":
            return Decision(0.0, 0, self._account(counts, 0, 0, bandwidth_est, rtt_s),
                            True, fixed)
        if policy == "mixed":  # NeuroSurgeon-for-ViT: pick the better endpoint
            lat_d = self._account(counts, n + 1, 0, bandwidth_est, rtt_s)
            lat_c = self._account(counts, 0, 0, bandwidth_est, rtt_s)
            s = n + 1 if lat_d <= lat_c else 0
            return Decision(0.0, s, min(lat_d, lat_c), True, fixed)
        raise ValueError(policy)

    # -- real-math execution (compiled-plan cache) ---------------------------
    def _exec_geometry(self, dec: Decision) -> tuple[tuple[int, ...], int]:
        """(merge schedule, split) to execute for a decision priced on the
        timing profile: the decision itself when the executed model is the
        profiled one (the served ViT-L@384); a smaller stand-in (CPU
        rehearsal) gets (alpha, split) remapped onto its geometry."""
        cfg, p = self.model_cfg, self.profile
        if (cfg.n_layers, cfg.num_tokens) == (p.n_layers, p.x0):
            return tuple(dec.schedule), dec.split
        n_exec = cfg.n_layers
        sched_exec = tuple(pruning.make_schedule(
            p.schedule_kind, dec.alpha, n_exec, cfg.num_tokens))
        if dec.split >= p.n_layers + 1:
            return sched_exec, n_exec + 1
        return sched_exec, min(round(dec.split * n_exec / p.n_layers), n_exec)

    def execute_device(self, dec: Decision, images: jax.Array) -> tuple[ExecPlan, float | None]:
        """Run the device partition (compiled) and encode the wire payload.
        Returns the pending ExecPlan and the measured payload size (None = no
        transfer)."""
        n_exec = self.model_cfg.n_layers
        sched_exec, split_exec = self._exec_geometry(dec)
        s = n_exec if split_exec == n_exec + 1 else split_exec
        dev_fn = self.plan_cache.device_fn(self.model_cfg, sched_exec, s, images)
        x, sizes = dev_fn(self.params, images)
        payload_bytes = None
        if split_exec not in (0, n_exec + 1):
            payload = compression.activation_payload(
                x, quantize=self.cfg.quantize_payload)
            x = jnp.asarray(compression.decode_activation(payload),
                            dtype=self.model_cfg.dtype)
            payload_bytes = payload.nbytes
        return ExecPlan(sched_exec, split_exec, x=x, sizes=sizes), payload_bytes

    def finish_execution(self, plan: ExecPlan) -> None:
        """Run a pending cloud partition inline (single-stream path; the fleet
        batches same-geometry plans via ``run_cloud_batch`` instead)."""
        if plan.logits is not None:
            return
        run_cloud_batch(self.plan_cache, self.model_cfg, self.params, [plan])

    # -- per-frame step (shared by single-stream and fleet paths) -------------
    def plan_frame(self, frame_idx: int, trace: NetworkTrace, policy: str,
                   estimator: HarmonicMeanEstimator,
                   images: jax.Array | None = None,
                   defer_cloud: bool = False) -> FrameStep:
        """``decide -> account`` for one frame. Pure with respect to engine
        state: the caller owns the estimator and must ``observe`` the returned
        ``bandwidth_bps`` after the frame (the fleet keeps one estimator per
        stream). With ``defer_cloud=True`` an executed frame's cloud partition
        is left pending in ``step.exec_plan`` for batched dispatch."""
        b_est = estimator.estimate()
        dec = self._decide(policy, b_est, trace.rtt_s)
        counts = self._counts_for(dec.schedule)
        b_true = trace.at(frame_idx)

        payload_bytes = self._payload_bytes(counts, dec.split)
        exec_plan = None
        if self.cfg.execute and self.params is not None and images is not None:
            exec_plan, measured = self.execute_device(dec, images)
            if measured is not None:
                payload_bytes = measured
            n_exec = self.model_cfg.n_layers
            if not defer_cloud or exec_plan.split == n_exec + 1:
                # device-only frames never enter the shared cloud tier, so
                # their (head-only) cloud program always completes inline
                self.finish_execution(exec_plan)

        bd = self.account_breakdown(counts, dec.split, payload_bytes, b_true,
                                    trace.rtt_s)
        acc = self.acc.accuracy(self.profile.x0, dec.schedule) \
            * self.cfg.accuracy_scale
        return FrameStep(decision=dec, breakdown=bd, payload_bytes=payload_bytes,
                         bandwidth_bps=b_true, accuracy=acc, exec_plan=exec_plan)

    def overhead_s(self, step: FrameStep) -> float:
        return step.decision.scheduler_overhead_s \
            if self.cfg.include_scheduler_overhead else 0.0

    def frame_result(self, step: FrameStep, queue_s: float = 0.0) -> FrameResult:
        """Stamp a planned frame into a result; ``queue_s`` is any extra delay
        the shared cloud tier added on top of the standalone latency."""
        lat = step.breakdown.total_s + self.overhead_s(step) + queue_s
        logits = step.exec_plan.logits if step.exec_plan is not None else None
        return FrameResult(
            latency_s=lat, violated=lat > self.cfg.sla_s,
            deviation=max(0.0, (lat - self.cfg.sla_s) / self.cfg.sla_s),
            alpha=step.decision.alpha, split=step.decision.split,
            accuracy=step.accuracy, payload_bytes=step.payload_bytes,
            bandwidth_bps=step.bandwidth_bps, queue_s=queue_s, logits=logits)

    # -- main loop ------------------------------------------------------------
    def run_trace(self, trace: NetworkTrace, n_frames: int, policy: str = "janus",
                  images: jax.Array | None = None) -> RunStats:
        self._estimator = HarmonicMeanEstimator(
            cold_start_bps=float(np.mean(trace.bps)))
        frames: list[FrameResult] = []
        for i in range(n_frames):
            step = self.plan_frame(i, trace, policy, self._estimator, images=images)
            frames.append(self.frame_result(step))
            self._estimator.observe(step.bandwidth_bps)
        return RunStats(frames)
