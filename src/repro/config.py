"""Frozen-dataclass configuration system + registry.

Every run is described by a :class:`RunConfig` tree.  Configs are immutable;
``replace()`` (re-exported from dataclasses) derives variants.  Architecture
configs live in ``repro.configs`` and register themselves in ``ARCH_REGISTRY``.
"""
from __future__ import annotations

import functools
import warnings
from dataclasses import dataclass, field, replace  # noqa: F401  (replace re-exported)
from typing import Any, Callable, Mapping, Optional, Tuple

# ---------------------------------------------------------------------------
# Model configuration
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class AttentionConfig:
    """Attention flavour. kind: mha | gqa | mla | none (attention-free)."""

    kind: str = "gqa"
    num_heads: int = 8
    num_kv_heads: int = 8
    head_dim: int = 128
    # MLA (multi-head latent attention, MiniCPM3/DeepSeek style)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    causal: bool = True
    rope: bool = True
    rope_theta: float = 10_000.0

    @property
    def q_heads_per_kv(self) -> int:
        return self.num_heads // max(self.num_kv_heads, 1)


@dataclass(frozen=True)
class MoEConfig:
    """Token-choice top-k mixture of experts."""

    num_experts: int = 8
    top_k: int = 2
    expert_d_ff: int = 512
    num_shared_experts: int = 0
    shared_d_ff: int = 0
    router_jitter: float = 0.0
    load_balance_coef: float = 0.01
    # dispatch implementation: "einsum" (dense one-hot (g,E,C) dispatch
    # tensors — simple, but dispatch FLOPs/memory scale with E*C*d and can
    # dwarf the expert FFN for many-small-expert configs) or "gather"
    # (scatter/gather routing — O(g*K*d), the optimized path; see §Perf).
    dispatch: str = "einsum"
    # token-group size for routing; dispatch memory ~ group*E*capacity (einsum)
    # or group*top_k*d (gather).  Sized per-arch so groups fit VMEM-scale.
    group_size: int = 4096
    # pad the stacked expert weights to this count (0 = no padding) so the
    # expert dim divides the TP/EP mesh axis: 40 or 60 experts cannot shard
    # over a 16-wide axis and would silently replicate (16x compute waste);
    # padded experts receive no tokens and exist only for divisibility.
    pad_experts_to: int = 0


@dataclass(frozen=True)
class SSMConfig:
    """Mamba-1 selective scan (for jamba) — d_inner = expand * d_model."""

    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 0  # 0 -> ceil(d_model / 16)


@dataclass(frozen=True)
class RWKVConfig:
    """RWKV-6 'Finch' data-dependent decay."""

    head_dim: int = 64
    decay_lora: int = 64
    token_shift: bool = True


@dataclass(frozen=True)
class ModelConfig:
    name: str = "model"
    family: str = "decoder"  # decoder | encdec | resnet | rwkv | hybrid
    num_layers: int = 4
    d_model: int = 256
    d_ff: int = 1024
    vocab_size: int = 32_000
    attention: Optional[AttentionConfig] = None
    moe: Optional[MoEConfig] = None
    ssm: Optional[SSMConfig] = None
    rwkv: Optional[RWKVConfig] = None
    mlp: str = "swiglu"  # swiglu | relu2 | gelu
    norm: str = "rmsnorm"  # rmsnorm | layernorm
    # hybrid (jamba): per-layer mixer pattern, period repeats over num_layers.
    # entries: "attn" | "mamba"; moe_period: every k-th layer uses MoE MLP.
    hybrid_attn_period: int = 0  # 0 = not hybrid; jamba: 8 with attn at index 3
    hybrid_attn_index: int = 3
    moe_every_k: int = 0  # 0 = never; jamba: 2
    # enc-dec
    num_encoder_layers: int = 0
    encoder_seq_len: int = 0  # whisper: 1500 frames
    # vlm stub
    num_patch_tokens: int = 0  # internvl: 1024 patch embeddings
    frontend_dim: int = 0  # dim of precomputed frontend embeddings (0 = d_model)
    # resnet
    resnet_blocks: Tuple[int, ...] = ()
    resnet_width: int = 64
    num_classes: int = 1000
    image_size: int = 224
    tie_embeddings: bool = False
    dtype: str = "bfloat16"
    param_dtype: str = "float32"
    remat: bool = True
    scan_layers: bool = True
    # which attention implementation the model uses: "ref" (jnp), "pallas"
    # (the flash kernel, compiled for the TPU) or "pallas_interpret" (the
    # same kernel in interpret mode, for CPU hosts)
    attention_impl: str = "ref"

    @property
    def head_dim(self) -> int:
        a = self.attention
        if a is None:
            return 0
        if a.kind == "mla":
            return a.qk_nope_head_dim + a.qk_rope_head_dim
        return a.head_dim

    def param_count(self) -> int:
        """Analytic parameter count (used for 6ND model-flops accounting)."""
        from repro.models.counting import count_params  # lazy, avoids cycle

        return count_params(self)


# ---------------------------------------------------------------------------
# Input shapes (assigned per-arch shape set)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


TRAIN_4K = ShapeConfig("train_4k", 4_096, 256, "train")
PREFILL_32K = ShapeConfig("prefill_32k", 32_768, 32, "prefill")
DECODE_32K = ShapeConfig("decode_32k", 32_768, 128, "decode")
LONG_500K = ShapeConfig("long_500k", 524_288, 1, "decode")

SHAPES: Mapping[str, ShapeConfig] = {
    s.name: s for s in (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)
}

# ---------------------------------------------------------------------------
# Data pipeline configuration (the paper's knobs, Table 4/5)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class CacheConfig:
    """Cache-tier block of a :class:`StoreConfig` (paper §2.4; Varnish
    analogue).  When both ``memory_bytes`` and ``dir`` are set, build_store
    assembles one two-tier TieredCacheStore (memory LRU over bounded disk)
    instead of nesting single-tier caches."""

    memory_bytes: int = 0  # memory tier capacity; 0 = no memory tier
    dir: str = ""  # disk tier directory; "" = no disk tier
    disk_bytes: int = 0  # disk tier capacity; 0 = unbounded (legacy)
    # memory-tier lock striping.  Default 1 = exact global LRU with items
    # cacheable up to the full capacity (the legacy CachedStore semantics).
    # Raising it trades strict LRU for less lock contention AND caps the
    # largest cacheable item at memory_bytes // shards — opt in only
    # when single objects are far smaller than the memory budget.
    shards: int = 1
    # disk-tier admission: admit-all | size-threshold | second-hit | tinylfu
    admission: str = "admit-all"
    admission_max_item_bytes: int = 1 << 20  # size-threshold policy cutoff
    # multi-host disk-tier coordination (repro.core.coord) when several
    # processes/hosts point ``dir`` at one shared directory:
    #   ""        — off: in-process accounting only (single-host, the default)
    #   "journal" — shared accounting: one fcntl-locked byte journal under
    #               dir/.coord bounds the tier across all writers
    #   "shard"   — partitioned keyspace: this host only caches keys where
    #               host_shard(key, n_hosts) == host_id (capacity is per-host)
    #               but opportunistically reads peers' entries off the shared
    #               disk
    coord: str = ""
    coord_host_id: int = 0
    coord_num_hosts: int = 1


@dataclass(frozen=True)
class StoreConfig:
    kind: str = "s3sim"  # memory | localfs | s3sim | synth
    root: str = ""  # for localfs
    # SimulatedS3 latency model (lognormal) — defaults calibrated so that the
    # paper's phenomenology reproduces at benchmark scale (see DESIGN.md §2).
    latency_mean_s: float = 0.08
    latency_sigma: float = 0.5
    bandwidth_per_conn: float = 25e6  # bytes/s per connection
    nic_bandwidth: float = 1.2e9  # bytes/s aggregate
    max_connections: int = 256
    failure_rate: float = 0.0
    # congestion collapse model: when the NIC is oversubscribed (more active
    # transfers than nic_bandwidth / bandwidth_per_conn supports), each GET's
    # service time is additionally scaled by (oversubscription)**overload_penalty
    # — the queueing/bufferbloat tail real links exhibit.  0 = off (the
    # legacy monotone model, where extra concurrency never hurts).
    overload_penalty: float = 0.0
    # cache tiers (see CacheConfig).  The historical flat ``cache_*`` kwargs
    # still construct the nested form through a deprecation shim; reads of
    # the old flat names delegate below.
    cache: CacheConfig = CacheConfig()

    # -- legacy flat reads (the write path is shimmed in __init__) ----------
    @property
    def cache_bytes(self) -> int:
        return self.cache.memory_bytes

    @property
    def cache_dir(self) -> str:
        return self.cache.dir

    @property
    def disk_cache_bytes(self) -> int:
        return self.cache.disk_bytes

    @property
    def cache_shards(self) -> int:
        return self.cache.shards

    @property
    def cache_admission(self) -> str:
        return self.cache.admission

    @property
    def admission_max_item_bytes(self) -> int:
        return self.cache.admission_max_item_bytes

    @property
    def cache_coord(self) -> str:
        return self.cache.coord

    @property
    def cache_coord_host_id(self) -> int:
        return self.cache.coord_host_id

    @property
    def cache_coord_num_hosts(self) -> int:
        return self.cache.coord_num_hosts


# Deprecation shim: StoreConfig grew 9 flat cache fields over PRs 2-3; they
# now live in CacheConfig.  Old call sites keep working — each flat kwarg
# warns once and is folded into the nested sub-config — and
# ``dataclasses.replace`` passes the nested field straight through, so the
# shim never re-fires on derived configs.  Migration note in README
# ("Online serving read path").
_LEGACY_CACHE_KWARGS = {
    "cache_bytes": "memory_bytes",
    "cache_dir": "dir",
    "disk_cache_bytes": "disk_bytes",
    "cache_shards": "shards",
    "cache_admission": "admission",
    "admission_max_item_bytes": "admission_max_item_bytes",
    "cache_coord": "coord",
    "cache_coord_host_id": "coord_host_id",
    "cache_coord_num_hosts": "coord_num_hosts",
}

_store_config_init = StoreConfig.__init__


@functools.wraps(_store_config_init)
def _store_config_shim_init(self, *args: Any, **kwargs: Any) -> None:
    legacy = {}
    for flat, nested in _LEGACY_CACHE_KWARGS.items():
        if flat in kwargs:
            warnings.warn(
                f"StoreConfig({flat}=...) is deprecated and will be removed;"
                f" pass cache=CacheConfig({nested}=...) instead",
                DeprecationWarning, stacklevel=2,
            )
            legacy[nested] = kwargs.pop(flat)
    if legacy:
        cache = kwargs.get("cache")
        kwargs["cache"] = replace(
            cache if cache is not None else CacheConfig(), **legacy
        )
    _store_config_init(self, *args, **kwargs)


StoreConfig.__init__ = _store_config_shim_init  # type: ignore[method-assign]


@dataclass(frozen=True)
class AutotuneConfig:
    """Closed-loop knob control for the loader (online analogue of the
    Fig. 10/11 grid search).

    A hill-climbing controller with hysteresis observes windowed throughput
    (``Tracer`` get_batch spans) plus store/fetch signals and adjusts, at a
    safe between-batch boundary: per-worker fetch concurrency, the prefetch
    outstanding window, hedging on/off, and (when attached) the device
    prefetch ring depth.  All knobs are clamped to the bounds below.
    """

    enabled: bool = False
    # measurement window: closes after at least `interval_batches` batches
    # AND `min_window_s` wall time.  The wall-time floor matters: delivery is
    # bursty (the reorder buffer releases several batches at once), so a
    # batch-count-only window can span microseconds and measure buffer pops
    # instead of pipeline production rate.
    interval_batches: int = 4
    min_window_s: float = 0.2
    # measured windows to observe before the first probe (the first window is
    # warped by the prefetch burst + worker startup)
    warmup_windows: int = 1
    # accept a move only if windowed throughput improves by this fraction;
    # revert if it regresses by more than it (hysteresis dead-band)
    rel_improvement: float = 0.05
    # knob bounds (inclusive)
    min_fetch_workers: int = 1
    max_fetch_workers: int = 64
    min_outstanding: int = 1
    max_outstanding: int = 64
    min_device_prefetch: int = 1
    max_device_prefetch: int = 8
    # per-knob coarse->fine step schedule for integer knobs: each knob starts
    # at the first (coarse) factor and drops to the next finer one after a
    # revert/hold on that knob; a rearm (regime change) resets to coarse.
    # () derives (2 * step_factor, step_factor) so a bare step_factor keeps
    # its legacy meaning as the *fine* step.
    step_schedule: Tuple[int, ...] = ()
    # multiplicative fine step for integer knobs (value *= step / value //= step)
    step_factor: int = 2
    # allow the controller to trial-toggle hedged requests once concurrency
    # knobs have plateaued (threaded impl only)
    tune_hedge: bool = False
    # consecutive plateau windows before the controller goes quiescent
    patience: int = 3
    # jump back to the best settled state when a window collapses below half
    # of its throughput.  Right for stationary measurement (the collapse IS
    # the walk's fault); disable when the environment itself is non-stationary
    # (shared CPUs, phase-shifting load) — there a collapse says nothing
    # about the knobs and restoring just thrashes them.
    collapse_restore: bool = True
    # exploration heartbeat: while quiescent, re-probe once every this many
    # windows (0 = off).  Escapes premature parking after early noise
    # reverts — a collapse-based re-arm alone cannot detect "parked at a
    # stable but suboptimal point".  A failed heartbeat probe re-quiesces
    # immediately; an accepted one resumes full climbing.
    reprobe_windows: int = 8
    # accelerator-utilization gate: when the controller has a utilization
    # signal (Trainer wires repro.core.utilization.recent_busy_fraction) and
    # the training step is busier than this fraction, upward probes are
    # skipped — don't buy loader throughput the accelerator can't eat.
    # 0 disables the gate.
    util_gate: float = 0.9
    # cache-tier knobs (attached when the dataset's store stack contains a
    # TieredCacheStore).  Capacity knobs exist only when the matching
    # max_*_cache_bytes names an explicit ceiling ABOVE the configured
    # capacity (default 0 = no capacity knob): growth is almost always
    # throughput-positive, so a default ceiling would let the hill climber
    # silently walk a cache the user sized for their RAM/disk up to it.
    # The admission-policy knob is attached whenever a disk tier exists.
    tune_cache: bool = True
    min_memory_cache_bytes: int = 1 << 20
    max_memory_cache_bytes: int = 0
    min_disk_cache_bytes: int = 1 << 22
    max_disk_cache_bytes: int = 0
    tune_admission: bool = True
    # cache-knob cadence.  Capacity knobs pay off on *epoch* timescales in
    # full-pass regimes (a shuffled pass has no intra-epoch repeats, so a
    # bigger cache only shows up one epoch later — see bench_cache):
    #   "batch" — cache knobs ride the per-batch controller (legacy; right
    #             for within-epoch-repeat workloads)
    #   "epoch" — the loader runs a second controller for the cache knobs,
    #             fed once per completed epoch, judging on
    #             cache_epoch_windows-epoch throughput windows
    cache_cadence: str = "batch"
    cache_epoch_windows: int = 2
    # multi-host cooperative tuning (repro.core.coord.UpProbeLease): when
    # coord_dir names a directory shared by co-located hosts, upward
    # concurrency/hedging probes require holding the fleet-wide up-probe
    # lease — one tenant probes a saturated NIC while the others hold or
    # refine downward.  "" = off (single-host, the default; behaviour is
    # bit-identical to a lease-free controller).  A crashed holder's lease
    # expires after coord_ttl_s.
    coord_dir: str = ""
    coord_ttl_s: float = 30.0
    # staged-pipeline stage knobs (LoaderConfig.pipeline): CPU executor width
    # and the fetch->decode queue depth.  The IO executor reuses the
    # min/max_fetch_workers bounds above — it gates the same resource (in-
    # flight GETs) the per-worker fetch pool gated in the legacy path.
    min_cpu_workers: int = 1
    max_cpu_workers: int = 32
    min_stage_queue: int = 4
    max_stage_queue: int = 512
    # shm-transport slab pressure knob (PipelineConfig.transport="shm"): the
    # controller caps how many of the preallocated slots each worker may use
    # (live, via a slab_cap message) — fewer slots = less memory pinned and
    # earlier pickle fallback; more slots = headroom for bursty decode.
    min_slab_slots: int = 4
    max_slab_slots: int = 512
    # -- objective ----------------------------------------------------------
    # "throughput" (default): score = windowed items/s (training loaders).
    # "latency": score = latency_target_s / windowed latency_quantile — the
    # serving read path feeds per-request latencies via on_request() and the
    # same hill climber MINIMIZES the tail by maximizing the inverted score.
    objective: str = "throughput"
    latency_target_s: float = 0.5  # the SLO target the p-quantile is scored against
    latency_quantile: float = 0.99
    # serve read-path knob bounds (objective="latency"): SLO hedge delay and
    # the single-flight coalesce result-hold window, in milliseconds.
    min_hedge_delay_ms: int = 1
    max_hedge_delay_ms: int = 5_000
    min_coalesce_ms: int = 1
    max_coalesce_ms: int = 5_000
    # sharded-delivery lane-skew gate: when stage_stats()["delivery"] reports
    # lane_skew (max-min composed batches across lanes) at or above this many
    # batches, upward probes are skipped — widening a pipeline whose lanes
    # already diverge just deepens the straggler imbalance; only downward
    # refinement runs until the lanes re-converge.  0 disables the gate.
    skew_gate: int = 0
    # shuffle-entropy floor (reorder="window" pipelines): when
    # stage_stats()["shuffle"] reports within-batch entropy below this value
    # (normalized 0..1), upward probes of the reorder_window knob are
    # skipped — a wider window buys throughput by stratifying batches by
    # completion time, and this floor makes that randomness loss a measured,
    # gated trade instead of an invisible one.  0.0 disables the gate.
    min_shuffle_entropy: float = 0.0
    # reorder_window knob bounds (window-mode pipelines only)
    min_reorder_window: int = 1
    max_reorder_window: int = 64
    # -- cooperative down-shedding (repro.core.coord.CongestionBoard) -------
    # AIMD across the fleet: a host whose window collapses below
    # shed_collapse_fraction of its best settled throughput posts a shed
    # event to coord_dir's CongestionBoard, and EVERY host (poster included)
    # multiplicatively cuts its concurrency knobs by shed_md_factor, holds
    # shed_hold_windows windows, then recovers additively toward the
    # pre-shed values over shed_recover_windows windows.  Per-host hill
    # climbing only gives back its own last probe step under collapse; the
    # board is what makes the whole fleet back off together.  0.0 = off
    # (the default: existing coord_dir fleets keep lease-gating only).
    # Requires coord_dir.
    shed_collapse_fraction: float = 0.0
    shed_md_factor: float = 0.5  # multiplicative-decrease factor per shed
    shed_hold_windows: int = 2  # windows to sit at the cut point
    shed_recover_windows: int = 8  # windows to climb back additively
    # fleet-wide shed rate limit: a collapse seen by N hosts injects ONE
    # shed event, not N stacked halvings (enforced under the board lock)
    shed_min_interval_s: float = 5.0


@dataclass(frozen=True)
class PipelineConfig:
    """Staged streaming pipeline (repro.core.pipeline): replaces the
    worker/fetcher path with an explicit stage graph (fetch-raw -> decode ->
    augment -> collate) on dedicated IO and CPU executors with sample-level
    out-of-order completion.  ``enabled=False`` (the default) keeps the
    legacy path untouched and bit-identical; the sub-config is truthy iff
    enabled, so ``if cfg.pipeline:`` reads the same either way."""

    enabled: bool = False
    # batch-assembly policy:
    #   "strict" — every batch holds exactly its sampler-assigned samples in
    #              sampler order, delivered in batch order (bit-identical to
    #              the legacy loader's stream)
    #   "window" — within each aligned group of `reorder_window` batches,
    #              batch slots are filled by whichever of the group's samples
    #              finish first (first-N-ready composition); a straggler only
    #              delays the last batch of its group, not its own batch
    reorder: str = "strict"
    reorder_window: int = 4
    # stage sizing.  io_workers 0 = derive: the IO gate starts at
    # num_workers * num_fetch_workers (the legacy loader's total fetch
    # thread count) and, unless the autotuner owns it, widens from there
    # while observed GET latency sets the pace (never below that seed, at
    # most the outstanding sample window); >0 pins the width.  cpu_workers
    # 0 = 4.
    io_workers: int = 0
    cpu_workers: int = 0
    # CPU (decode+augment) stage executor:
    #   "thread"  — gated thread pool (legacy; right for GIL-releasing C
    #               decoders like libjpeg, zero serialization cost)
    #   "process" — spawn-based worker-process pool (escapes the GIL for
    #               pure-Python/GIL-holding decoders; requires the dataset's
    #               split path AND a picklable dataset — see README).  The
    #               pool persists across epochs on the loader; a crashed
    #               worker is respawned and only its in-flight sample is
    #               retried.  Datasets without the split path fall back to
    #               monolithic fetch exactly as with "thread".
    cpu_executor: str = "thread"
    # bounded fetch->decode queue (in samples).  A full queue blocks the IO
    # threads that try to feed it — that stall is the pipeline's
    # backpressure, and the depth is an autotune knob.
    stage_queue_depth: int = 64
    # process-stage result transport (cpu_executor="process" only):
    #   "pipe" — every decoded sample is pickled through the result pipe
    #            (legacy; fine at tens of kB, two full copies per sample)
    #   "shm"  — workers write decoded arrays into a preallocated per-worker
    #            shared-memory slab (slot-granular, generation-counted) and
    #            ship only (slot, dtype, shape, offset) handles over the
    #            pipe; the parent reads zero-copy views.  Oversized/ragged
    #            samples and slab pressure fall back to pickle per sample.
    transport: str = "pipe"
    # shm slab sizing: slots per worker slab and bytes per slot.  A slot
    # must hold one whole decoded sample (all arrays, padded to 64B each);
    # bigger samples take the pickle fallback.  slab_slots is an autotune
    # knob (AutotuneConfig.min/max_slab_slots).
    slab_slot_bytes: int = 1 << 20
    slab_slots: int = 32
    # pinned host staging (repro.core.staging): >0 collates batches directly
    # into a pool of this many reusable page-aligned host buffers that the
    # device-prefetch ring hands to device_put and recycles after transfer,
    # replacing the per-batch np.stack allocation+copy.  Only engages for
    # the default collate; 0 = off.
    staging_buffers: int = 0

    def __bool__(self) -> bool:
        return self.enabled


@dataclass(frozen=True)
class DeliverySpec:
    """How assembled batches reach the consumer (repro.core.delivery).

    * ``host`` (default) — one host-resident numpy batch per step; the
      consumer (or the device-prefetch ring) moves it to devices.
    * ``sharded`` — one assembler lane per addressable slice of ``mesh``
      along ``axis``; each lane collates its contiguous sub-batch and
      device-puts it to its own device(s), and the lanes are composed into a
      device-sharded global ``jax.Array`` via
      ``jax.make_array_from_single_device_arrays`` (process-local shards
      only — no gather).  Requires the staged pipeline with strict reorder.

    ``mesh`` is a ``jax.sharding.Mesh`` (kept opaque here so the config
    layer stays jax-free); ``coord_dir`` names a directory shared by
    co-located hosts so per-lane resume cursors are pinned fleet-wide
    (repro.core.delivery.ShardCursorBoard over the PR-3 coord layer)."""

    kind: str = "host"  # host | sharded
    axis: str = "data"  # mesh axis the global batch dim shards over
    mesh: Any = None  # jax.sharding.Mesh (required for kind="sharded")
    coord_dir: str = ""  # multi-host cursor alignment ("" = single host)

    @staticmethod
    def host() -> "DeliverySpec":
        return DeliverySpec()

    @staticmethod
    def sharded(mesh: Any, axis: str = "data",
                coord_dir: str = "") -> "DeliverySpec":
        return DeliverySpec(kind="sharded", axis=axis, mesh=mesh,
                            coord_dir=coord_dir)


@dataclass(frozen=True)
class ElasticConfig:
    """Elastic fleet membership + work claiming (repro.core.elastic).

    When enabled, the loader joins a lease-based ``MembershipBoard`` under
    ``coord_dir`` and replaces static batch sharding with claim-based
    scheduling over an ``EpochShardBoard``: the epoch's batches are split
    into shards of ``shard_batches`` that live hosts claim under TTL
    leases, so hosts may join, leave, or crash mid-epoch and the *union*
    of delivered batches still covers the epoch exactly (a dead host's
    in-flight shard is resumed by a survivor at its last confirmed batch —
    at-least-once for the unconfirmed tail, never lost).  The sub-config
    is truthy iff enabled, so ``if cfg.elastic:`` reads naturally."""

    enabled: bool = False
    coord_dir: str = ""  # shared directory (required when enabled)
    lease_ttl_s: float = 10.0  # membership + shard-claim lease TTL
    heartbeat_interval_s: float = 2.0  # max staleness of our own lease
    shard_batches: int = 8  # claim granularity (batches per shard)
    claim_poll_s: float = 0.05  # wait between claim attempts when starved

    def __bool__(self) -> bool:
        return bool(self.enabled)


_PREDICATE_OPS = ("==", "!=", "<", "<=", ">", ">=", "in", "not_in")


@dataclass(frozen=True)
class SamplerPredicate:
    """Callable-free sampler predicate for columnar pushdown.

    ``clauses`` is an AND-list of ``(field, op, value)`` tuples over a
    dataset's metadata columns, e.g. ``(("label", "in", (0, 1, 2)),
    ("length", "<", 65536))``.  Tuples (not callables) keep predicates
    picklable, checkpointable, and evaluable against chunk statistics —
    the loader hands them to the dataset's ``predicate_mask`` so rejected
    rows' bytes are never requested from the store.

    ``schedule`` optionally re-declares the clause list per epoch for
    curriculum filtering: ``((epoch, clauses), ...)`` — the entry with the
    largest ``epoch <= current`` wins; before the first entry, ``clauses``
    applies.  Epoch masks are pure functions of (predicate, epoch), so
    strict-mode resume cursors replay the identical filtered stream.
    """

    clauses: Tuple[Tuple[str, str, Any], ...] = ()
    schedule: Tuple[Tuple[int, Tuple[Tuple[str, str, Any], ...]], ...] = ()

    def __post_init__(self) -> None:
        for cls in (self.clauses, *(cl for _, cl in self.schedule)):
            for c in cls:
                if len(c) != 3 or not isinstance(c[0], str) or c[1] not in _PREDICATE_OPS:
                    raise ValueError(
                        f"predicate clause must be (field, op, value) with op "
                        f"in {_PREDICATE_OPS}, got {c!r}")
                if callable(c[2]):
                    raise ValueError(f"predicate values must be data, not "
                                     f"callables: {c!r}")

    def clauses_for_epoch(self, epoch: int) -> Tuple[Tuple[str, str, Any], ...]:
        out = self.clauses
        for e, cls in sorted(self.schedule, key=lambda t: t[0]):
            if epoch >= e:
                out = tuple(cls)
        return out

    def __bool__(self) -> bool:
        return bool(self.clauses or self.schedule)


@dataclass(frozen=True)
class LoaderConfig:
    impl: str = "threaded"  # vanilla | threaded | asyncio
    batch_size: int = 256
    num_workers: int = 4
    prefetch_factor: int = 4
    num_fetch_workers: int = 16
    batch_pool: int = 0  # >0 enables batch disassembly (threaded impl only)
    lazy_init: bool = True
    pin_device: bool = False  # device prefetch ring (batch_to_device overlap)
    device_prefetch: int = 2
    drop_last: bool = True
    shuffle: bool = True
    seed: int = 0
    # straggler mitigation: hedge a fetch when it exceeds p95 * hedge_factor
    hedge_requests: bool = False
    hedge_factor: float = 3.0
    hedge_min_s: float = 0.05
    timeout_s: float = 120.0
    # staged streaming pipeline (see PipelineConfig).  The legacy flat
    # kwargs (pipeline=<bool>, reorder=..., io_workers=..., ...) still
    # construct the nested form through a deprecation shim; reads of the old
    # flat names delegate below.
    pipeline: PipelineConfig = PipelineConfig()
    # batch delivery contract (see DeliverySpec): host-resident batches
    # (default) or device-sharded global arrays assembled per mesh lane
    delivery: DeliverySpec = DeliverySpec()
    # columnar predicate pushdown (see SamplerPredicate): filters the epoch
    # stream at the sampler via dataset metadata, so rejected rows are never
    # fetched.  None = unfiltered.  Requires a dataset with predicate
    # metadata (repro.data.columnar.ColumnarImageDataset).
    sampler: Optional[SamplerPredicate] = None
    # online knob control (off by default: behaviour is bit-identical to a
    # statically configured loader when disabled)
    autotune: AutotuneConfig = AutotuneConfig()
    # elastic fleet membership + claim-based batch scheduling (see
    # ElasticConfig).  Off by default: static host_id/num_hosts sharding.
    elastic: ElasticConfig = ElasticConfig()

    # -- legacy flat reads (the write path is shimmed in __init__) ----------
    @property
    def reorder(self) -> str:
        return self.pipeline.reorder

    @property
    def reorder_window(self) -> int:
        return self.pipeline.reorder_window

    @property
    def io_workers(self) -> int:
        return self.pipeline.io_workers

    @property
    def cpu_workers(self) -> int:
        return self.pipeline.cpu_workers

    @property
    def cpu_executor(self) -> str:
        return self.pipeline.cpu_executor

    @property
    def stage_queue_depth(self) -> int:
        return self.pipeline.stage_queue_depth


# Deprecation shim: LoaderConfig grew ~7 flat pipeline fields over PRs 4-5;
# they now live in PipelineConfig.  Old call sites keep working — each flat
# kwarg warns once and is folded into the nested sub-config — and
# ``dataclasses.replace`` passes the nested fields straight through, so the
# shim never re-fires on derived configs.  Removal note in README
# ("Sharded delivery & the loader API").
_LEGACY_PIPELINE_KWARGS = (
    "reorder", "reorder_window", "io_workers", "cpu_workers",
    "cpu_executor", "stage_queue_depth",
)

_loader_config_init = LoaderConfig.__init__


@functools.wraps(_loader_config_init)
def _loader_config_shim_init(self, *args: Any, **kwargs: Any) -> None:
    legacy = {}
    for name in _LEGACY_PIPELINE_KWARGS:
        if name in kwargs:
            warnings.warn(
                f"LoaderConfig({name}=...) is deprecated and will be removed;"
                f" pass pipeline=PipelineConfig({name}=...) instead",
                DeprecationWarning, stacklevel=2,
            )
            legacy[name] = kwargs.pop(name)
    pipe = kwargs.get("pipeline")
    if isinstance(pipe, bool):
        warnings.warn(
            "LoaderConfig(pipeline=<bool>) is deprecated and will be removed;"
            " pass pipeline=PipelineConfig(enabled=...) instead",
            DeprecationWarning, stacklevel=2,
        )
        kwargs["pipeline"] = PipelineConfig(enabled=pipe, **legacy)
    elif legacy:
        kwargs["pipeline"] = replace(
            pipe if pipe is not None else PipelineConfig(), **legacy
        )
    _loader_config_init(self, *args, **kwargs)


LoaderConfig.__init__ = _loader_config_shim_init  # type: ignore[method-assign]


@dataclass(frozen=True)
class TenantPolicy:
    """Per-tenant admission/fairness policy on the serving read path.

    Budgets meter the *shared* tiers: bytes served from the disk tier or
    fetched from origin debit the tenant's token bucket (memory-tier hits are
    free — they contend on nothing).  A tenant over budget blocks before
    issuing backend I/O until the bucket refills, so one hot tenant cannot
    starve the rest of disk/NIC service.  ``tenant="*"`` is the default
    policy for tenants without an explicit entry."""

    tenant: str = "*"
    rate_bytes_per_s: float = 0.0  # sustained budget; 0 = unmetered
    burst_bytes: int = 0  # bucket depth; 0 derives one second of rate
    max_inflight: int = 0  # concurrent backend fetches; 0 = unlimited


@dataclass(frozen=True)
class ServeSpec:
    """Online-serving surface (repro.serve): inference engine slots plus the
    multi-tenant read path (single-flight coalescing, tenant fairness, SLO
    hedging — see README "Online serving read path").

    The historical flat ``ServeEngine(cfg, params, num_slots=..., max_len=...)``
    kwargs still work through a warn-once deprecation shim; new call sites
    pass ``spec=ServeSpec(...)`` and ``replace()`` derives variants silently.
    """

    # -- engine (continuous-batching slots) ---------------------------------
    num_slots: int = 4
    max_len: int = 512
    # -- read path ----------------------------------------------------------
    # single-flight coalescing: concurrent misses on one key share a single
    # backend fetch, and the completed result is held for this window so
    # bursts arriving just after completion still coalesce.  0 disables
    # coalescing entirely (every miss fetches — the uncoalesced baseline).
    coalesce_window_s: float = 0.05
    # hedged reads: "off" | "fixed" (constant hedge_delay_s) | "slo" (delay
    # derived from the live latency distribution vs slo_p99_s: fire the
    # duplicate at max(hedge_min_s, slo_p99_s - p50) so it can still finish
    # inside the SLO).
    hedge: str = "off"
    hedge_delay_s: float = 0.1  # "fixed" mode delay
    hedge_min_s: float = 0.005  # floor under the derived "slo" delay
    slo_p99_s: float = 0.5  # tail-latency objective the path is tuned against
    hedge_budget_fraction: float = 0.05  # max hedges per request, sustained
    # global backend concurrency cap (leader + hedge fetches)
    max_inflight: int = 64
    # per-tenant fairness policies; ("*" entry = default for unlisted tenants)
    tenants: Tuple[TenantPolicy, ...] = ()
    # latency-objective closed-loop control (AutotuneConfig.objective must be
    # "latency" when enabled here): tunes hedge delay, coalesce window, and —
    # when the store stack has a TieredCacheStore — the cache knobs against
    # the p99 target.
    autotune: AutotuneConfig = AutotuneConfig()


@dataclass(frozen=True)
class TrainConfig:
    optimizer: str = "adamw"  # adamw | adafactor | sgd
    learning_rate: float = 1e-3
    weight_decay: float = 1e-4
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    grad_clip: float = 1.0
    warmup_steps: int = 100
    schedule: str = "cosine"  # cosine | constant | linear
    total_steps: int = 1000
    microbatches: int = 1  # grad-accumulation via lax.scan
    grad_compression: str = "none"  # none | bf16 | int8_ef
    checkpoint_every: int = 200
    keep_checkpoints: int = 3
    log_every_n_steps: int = 10
    label_smoothing: float = 0.0


@dataclass(frozen=True)
class MeshConfig:
    shape: Tuple[int, ...] = (16, 16)
    axes: Tuple[str, ...] = ("data", "model")

    @property
    def num_devices(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n


SINGLE_POD_MESH = MeshConfig((16, 16), ("data", "model"))
MULTI_POD_MESH = MeshConfig((2, 16, 16), ("pod", "data", "model"))


@dataclass(frozen=True)
class RunConfig:
    model: ModelConfig
    shape: ShapeConfig = TRAIN_4K
    loader: LoaderConfig = LoaderConfig()
    store: StoreConfig = StoreConfig()
    train: TrainConfig = TrainConfig()
    mesh: MeshConfig = SINGLE_POD_MESH
    serve: ServeSpec = ServeSpec()


# public surface (tests/test_api_surface.py pins names + signatures)
__all__ = [
    "AttentionConfig",
    "AutotuneConfig",
    "CacheConfig",
    "DeliverySpec",
    "ElasticConfig",
    "LoaderConfig",
    "MeshConfig",
    "ModelConfig",
    "MoEConfig",
    "PipelineConfig",
    "RunConfig",
    "RWKVConfig",
    "SamplerPredicate",
    "ServeSpec",
    "ShapeConfig",
    "SSMConfig",
    "StoreConfig",
    "TenantPolicy",
    "TrainConfig",
    "arch_shapes",
    "get_arch",
    "list_archs",
    "register_arch",
    "replace",
]

# ---------------------------------------------------------------------------
# Architecture registry
# ---------------------------------------------------------------------------

ARCH_REGISTRY: dict[str, Callable[[], ModelConfig]] = {}
SMOKE_REGISTRY: dict[str, Callable[[], ModelConfig]] = {}


def register_arch(name: str, full: Callable[[], ModelConfig], smoke: Callable[[], ModelConfig]) -> None:
    ARCH_REGISTRY[name] = full
    SMOKE_REGISTRY[name] = smoke


def get_arch(name: str, smoke: bool = False) -> ModelConfig:
    import repro.configs  # noqa: F401  triggers registration

    reg = SMOKE_REGISTRY if smoke else ARCH_REGISTRY
    if name not in reg:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(reg)}")
    return reg[name]()


def list_archs() -> list[str]:
    import repro.configs  # noqa: F401

    return sorted(ARCH_REGISTRY)


def arch_shapes(cfg: ModelConfig) -> list[ShapeConfig]:
    """Which of the four assigned shapes apply to this architecture.

    long_500k needs sub-quadratic attention: run for SSM/hybrid archs
    (rwkv6, jamba), skip for pure full-attention archs (noted in DESIGN.md).
    resnet uses its own image shapes and is the paper's own model, not one of
    the 40 assigned cells.
    """
    if cfg.family == "resnet":
        return [TRAIN_4K]
    shapes = [TRAIN_4K, PREFILL_32K, DECODE_32K]
    if cfg.family in ("rwkv", "hybrid"):
        shapes.append(LONG_500K)
    return shapes
