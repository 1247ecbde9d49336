"""Env knob parsing.

Parity: ``horovod/common/utils/env_parser.cc`` + the knob list in
``common.h:61-87``.  All knobs use the ``HVD_`` prefix; the launcher's CLI
flags and YAML config map onto these (runner/config_parser.py), mirroring
the reference's three-layer config system (SURVEY.md §5 config row).
"""

from __future__ import annotations

import os

# Knob names (reference equivalents in comments).
FUSION_THRESHOLD = "HVD_FUSION_THRESHOLD"          # HOROVOD_FUSION_THRESHOLD
CYCLE_TIME = "HVD_CYCLE_TIME"                      # HOROVOD_CYCLE_TIME (ms)
CACHE_CAPACITY = "HVD_CACHE_CAPACITY"              # HOROVOD_CACHE_CAPACITY
HIERARCHICAL_ALLREDUCE = "HVD_HIERARCHICAL_ALLREDUCE"
HIERARCHICAL_ALLGATHER = "HVD_HIERARCHICAL_ALLGATHER"
TIMELINE = "HVD_TIMELINE"                          # HOROVOD_TIMELINE
TIMELINE_MARK_CYCLES = "HVD_TIMELINE_MARK_CYCLES"
STALL_CHECK_DISABLE = "HVD_STALL_CHECK_DISABLE"
STALL_CHECK_TIME = "HVD_STALL_CHECK_TIME_SECONDS"
STALL_SHUTDOWN_TIME = "HVD_STALL_SHUTDOWN_TIME_SECONDS"
AUTOTUNE = "HVD_AUTOTUNE"
AUTOTUNE_LOG = "HVD_AUTOTUNE_LOG"
AUTOTUNE_WARMUP_SAMPLES = "HVD_AUTOTUNE_WARMUP_SAMPLES"
AUTOTUNE_MAX_SAMPLES = "HVD_AUTOTUNE_MAX_SAMPLES"      # BAYES_OPT_MAX_SAMPLES
AUTOTUNE_SAMPLE_DURATION = "HVD_AUTOTUNE_SAMPLE_DURATION_SECONDS"
ADASUM_MODE = "HVD_ADASUM_MODE"
# Eager data plane (horovod_tpu.ops.cpu_backend; docs/performance.md).
# RING_SEGMENT_BYTES slices each ring hop's receive so reducing segment k
# overlaps receiving segment k+1 (0 = whole-chunk hops, no segmentation);
# SOCK_BUF_BYTES, when > 0, sets SO_SNDBUF/SO_RCVBUF on every data-plane
# socket (both the dialing and the accepting side).
RING_SEGMENT_BYTES = "HVD_RING_SEGMENT_BYTES"
SOCK_BUF_BYTES = "HVD_SOCK_BUF_BYTES"
# Same-host shm transport (horovod_tpu.utils.transport;
# docs/performance.md "Transport selection").  SHM_DISABLE forces every
# peer link onto TCP (the escape hatch for a bad shm path); SLOT_BYTES /
# SLOTS size each directed ring (per peer pair: 2 rings of SLOTS slots
# of SLOT_BYTES payload each, floors 4096 bytes / 2 slots).
SHM_DISABLE = "HVD_SHM_DISABLE"
SHM_SLOT_BYTES = "HVD_SHM_SLOT_BYTES"
SHM_SLOTS = "HVD_SHM_SLOTS"
# Shm seqlock wait policy (docs/performance.md "Transport selection").
# SHM_SPIN is the hot-spin iteration count before a wait starts
# yielding; SHM_SLEEP_US is the escalating-microsleep ceiling in
# microseconds.  Defaults adapt to the host's core count (spinning is
# only profitable when the peer can run WHILE we spin).
SHM_SPIN = "HVD_SHM_SPIN"
SHM_SLEEP_US = "HVD_SHM_SLEEP_US"
# Data-plane recovery ladder (docs/fault_tolerance.md "recovery
# ladder").  WIRE_CRC=1 arms the whole ladder: every data frame gains a
# CRC-32 + sequence trailer (mirrored in csrc/wire.h), a corrupt frame
# is NACKed and retransmitted from the sender's retained copy (at most
# HOP_RETRIES times per link before the link is declared corrupt), a
# dropped data socket is re-dialed for up to RECONNECT_TIMEOUT_S with
# the PR-1 backoff+jitter, and a faulted shm ring demotes its peer pair
# to TCP in place.  Off (default) = byte-identical seed framing and
# zero new threads.  LADDER_RETAIN bounds the per-link replay buffer
# (frames).
WIRE_CRC = "HVD_WIRE_CRC"
HOP_RETRIES = "HVD_HOP_RETRIES"
RECONNECT_TIMEOUT_S = "HVD_RECONNECT_TIMEOUT_S"
LADDER_RETAIN = "HVD_LADDER_RETAIN"
# Liveness / fault tolerance (PyEngine; 0 = heartbeats disabled).
# HOROVOD_HEARTBEAT_TIMEOUT is accepted as an alias of the HVD_ name.
HEARTBEAT_TIMEOUT = "HVD_HEARTBEAT_TIMEOUT"
HEARTBEAT_INTERVAL = "HVD_HEARTBEAT_INTERVAL"
# Collective deadlines (PyEngine data plane; docs/fault_tolerance.md).
# COLLECTIVE_TIMEOUT (seconds, 0 = off = block forever like the seed)
# bounds every eager collective: ring hops get per-phase socket
# deadlines, a local timeout is reported to the coordinator, and the
# gang agrees on a CollectiveTimeoutError naming the wedged rank(s).
# COLLECTIVE_PROBE_TIMEOUT is how long the coordinator's probe round
# waits for acks before ruling (default: half the collective timeout).
# SEND_WAIT_CAP_S is an always-on generous hard cap on PeerSender.wait
# so a dead sender thread can never hang a hop silently, even with the
# collective timeout off.
COLLECTIVE_TIMEOUT = "HVD_COLLECTIVE_TIMEOUT"
COLLECTIVE_PROBE_TIMEOUT = "HVD_COLLECTIVE_PROBE_TIMEOUT"
SEND_WAIT_CAP_S = "HVD_SEND_WAIT_CAP_S"
# Rendezvous KV client retry policy.
KV_RETRIES = "HVD_KV_RETRIES"
KV_TIMEOUT = "HVD_KV_TIMEOUT"
KV_RETRY_BASE_S = "HVD_KV_RETRY_BASE_S"
KV_RETRY_MAX_S = "HVD_KV_RETRY_MAX_S"
# Ordered rendezvous endpoint list "host:port,host:port" (primary
# first, warm standbys after); unset = single HVD_RENDEZVOUS_ADDR/PORT.
KV_ADDRS = "HVD_KV_ADDRS"
# Launcher host blacklist (relaunch path).
BLACKLIST_THRESHOLD = "HVD_BLACKLIST_THRESHOLD"
BLACKLIST_COOLDOWN_S = "HVD_BLACKLIST_COOLDOWN_S"
# Elastic training (horovod_tpu.elastic; docs/elastic.md).  EPOCH is the
# gang's membership incarnation (stamped on every wire list frame);
# MIN_NP/MAX_NP bound the re-formed world; JOINER marks a late worker
# that waits for an epoch assignment instead of bootstrapping at rank 0;
# UID is a stable worker identity across incarnations; the two intervals
# pace the commit-time membership check and the driver's discovery poll.
ELASTIC_EPOCH = "HVD_ELASTIC_EPOCH"
ELASTIC_MIN_NP = "HVD_ELASTIC_MIN_NP"
ELASTIC_MAX_NP = "HVD_ELASTIC_MAX_NP"
ELASTIC_JOINER = "HVD_ELASTIC_JOINER"
ELASTIC_UID = "HVD_ELASTIC_UID"
ELASTIC_CHECK_INTERVAL_S = "HVD_ELASTIC_CHECK_INTERVAL_S"
ELASTIC_DISCOVERY_INTERVAL_S = "HVD_ELASTIC_DISCOVERY_INTERVAL_S"
HOST_DISCOVERY_SCRIPT = "HVD_HOST_DISCOVERY_SCRIPT"
# Hierarchical control plane (runtime_py.py; docs/fault_tolerance.md
# "Hierarchical control plane, fencing, and quorum").  CTRL_FANOUT caps
# how many children each per-host sub-coordinator folds (0 = the whole
# host; overflow children attach directly to the root).  QUORUM gates
# the elastic re-form majority check: with it on (default) a partition
# minority self-terminates (PARTITION_MINORITY) instead of re-forming a
# split-brain sibling gang.  CTRL_TREE is the tree kill-switch: the
# control tree needs every rank speaking the Python engine's tree tags,
# so a deliberately mixed-engine gang must set HVD_CTRL_TREE=0 to stay
# on the flat star (single-host gangs already do, automatically).
CTRL_FANOUT = "HVD_CTRL_FANOUT"
CTRL_TREE = "HVD_CTRL_TREE"
QUORUM = "HVD_QUORUM"
# Data-plane integrity (horovod_tpu.integrity; docs/fault_tolerance.md).
# POLICY gates the non-finite gradient guard in DistributedOptimizer
# (off | skip | zero | raise); LIMIT is the consecutive agreed-non-finite
# step count after which policy "raise" raises; AUDIT_INTERVAL paces the
# replica-divergence audit (steps; 0 = off); CKPT_KEEP is the verified
# checkpoint keep-last-K retention.
NONFINITE_POLICY = "HVD_NONFINITE_POLICY"
NONFINITE_LIMIT = "HVD_NONFINITE_LIMIT"
AUDIT_INTERVAL = "HVD_AUDIT_INTERVAL"
CKPT_KEEP = "HVD_CKPT_KEEP"
# Telemetry (horovod_tpu.telemetry; docs/metrics.md).  METRICS turns the
# registry on by itself; setting a PORT or FILE also enables it.  PORT is
# the per-worker debug server base port (bound at PORT + local_rank);
# FILE is the JSONL flush destination, written every INTERVAL seconds;
# STRAGGLER_WARN_MS is the consistent-last-rank skew threshold that
# triggers the STRAGGLER timeline record + warning.
METRICS = "HVD_METRICS"
METRICS_PORT = "HVD_METRICS_PORT"
METRICS_FILE = "HVD_METRICS_FILE"
METRICS_INTERVAL = "HVD_METRICS_INTERVAL"
STRAGGLER_WARN_MS = "HVD_STRAGGLER_WARN_MS"
# Gang-wide aggregation & streaming anomaly alerts (telemetry/aggregate.py;
# docs/metrics.md "Gang-wide aggregation & alerts").  AGG_INTERVAL paces
# rank 0's fold of every rank's snapshot into the single gang view
# served at /gang/metrics*.  The HVD_ALERT_* knobs tune the EWMA rules
# the anomaly engine evaluates each fold: EWMA_ALPHA is the trailing-
# baseline smoothing factor, WARMUP the folds observed before any rule
# may fire, COLLAPSE_FRAC the gang-throughput fraction of baseline below
# which throughput_collapse fires, SKEW_FACTOR/SKEW_FLOOR_MS the
# straggler-skew growth multiple and absolute floor, QUEUE_FACTOR /
# RETRY_FACTOR the growth multiples for admission-queue depth and
# ladder/KV retry rate, and SERVE_P99_MS the serve-SLO p99 ceiling in
# milliseconds (0 = rule off).
AGG_INTERVAL = "HVD_AGG_INTERVAL"
ALERT_EWMA_ALPHA = "HVD_ALERT_EWMA_ALPHA"
ALERT_WARMUP = "HVD_ALERT_WARMUP"
ALERT_COLLAPSE_FRAC = "HVD_ALERT_COLLAPSE_FRAC"
ALERT_SKEW_FACTOR = "HVD_ALERT_SKEW_FACTOR"
ALERT_SKEW_FLOOR_MS = "HVD_ALERT_SKEW_FLOOR_MS"
ALERT_QUEUE_FACTOR = "HVD_ALERT_QUEUE_FACTOR"
ALERT_RETRY_FACTOR = "HVD_ALERT_RETRY_FACTOR"
ALERT_SERVE_P99_MS = "HVD_ALERT_SERVE_P99_MS"
# Gang-wide distributed tracing (telemetry/trace.py; docs/timeline.md
# "Gang-wide tracing").  TRACE=1 makes EVERY rank stream structured
# spans (negotiate/pack/hop/unpack/callback, serving and elastic steps)
# to a per-rank JSONL file under TRACE_DIR (default: the working
# directory), merged/analyzed by tools/hvd_trace.py.  Workers piggyback
# a clock-offset ping on the control channel at bootstrap and then every
# TRACE_CLOCK_SYNC_CYCLES background cycles so the merged trace aligns
# per-rank monotonic clocks.  Unset (default) = provably zero-cost: no
# spans, no clock frames, allocation/syscall-identical hot path.
TRACE = "HVD_TRACE"
TRACE_DIR = "HVD_TRACE_DIR"
TRACE_CLOCK_SYNC_CYCLES = "HVD_TRACE_CLOCK_SYNC_CYCLES"
# Always-on flight recorder (telemetry/blackbox.py; docs/fault_tolerance.md
# "the black box").  Unlike HVD_TRACE this is ON by default: every rank
# keeps the last BLACKBOX_EVENTS events (default 512) in a fixed-capacity
# in-memory ring and dumps ``blackbox_rank<r>.json`` into BLACKBOX_DIR on
# any terminal failure, so the 3 a.m. crash ships its own evidence.
# BLACKBOX=0 turns the recorder off entirely.
BLACKBOX = "HVD_BLACKBOX"
BLACKBOX_EVENTS = "HVD_BLACKBOX_EVENTS"
BLACKBOX_DIR = "HVD_BLACKBOX_DIR"
# Inference serving (horovod_tpu.serving; docs/serving.md).  PORT is the
# rank-0 HTTP front door (0 = ephemeral); MAX_BATCH is the number of
# continuous-batching decode slots; MAX_QUEUE bounds the admission queue
# (a full queue sheds with HTTP 503).
SERVE_PORT = "HVD_SERVE_PORT"
SERVE_MAX_BATCH = "HVD_SERVE_MAX_BATCH"
SERVE_MAX_QUEUE = "HVD_SERVE_MAX_QUEUE"


def get_bool(name: str, default: bool = False) -> bool:
    v = os.environ.get(name)
    if v is None:
        return default
    return v.strip().lower() in ("1", "true", "yes", "on")


def get_int(name: str, default: int) -> int:
    v = os.environ.get(name)
    return int(v) if v not in (None, "") else default


def get_float(name: str, default: float) -> float:
    v = os.environ.get(name)
    return float(v) if v not in (None, "") else default


def get_str(name: str, default: str = "") -> str:
    return os.environ.get(name, default)


def fusion_threshold_bytes() -> int:
    """Default 64 MB, like the reference (operations.cc fusion threshold)."""
    return get_int(FUSION_THRESHOLD, 64 * 1024 * 1024)


def cycle_time_ms() -> float:
    """Background-loop cadence; reference default 5 ms (operations.cc:416)."""
    return get_float(CYCLE_TIME, 5.0)


def ring_segment_bytes() -> int:
    """Ring-hop segment size; 0 (default) disables segmentation."""
    return max(0, get_int(RING_SEGMENT_BYTES, 0))


def shm_disabled() -> bool:
    """True when the same-host shm transport is forced off (escape
    hatch: every peer link falls back to TCP)."""
    return get_bool(SHM_DISABLE, False)


def shm_slot_bytes() -> int:
    """Payload bytes per shm ring slot; floor 4096."""
    return max(4096, get_int(SHM_SLOT_BYTES, 256 * 1024))


def shm_slots() -> int:
    """Slots per directed shm ring; floor 2 (writer needs one slot in
    flight while the reader drains another)."""
    return max(2, get_int(SHM_SLOTS, 16))


def shm_spin() -> int:
    """Hot-spin iterations before a shm wait starts yielding.  Spinning
    only pays when a spare core can run the peer meanwhile, so the
    default is 64 on multi-core hosts and 0 on a single core."""
    cpus = os.cpu_count() or 1
    return max(0, get_int(SHM_SPIN, 64 if cpus > 1 else 0))


def shm_sleep_us() -> int:
    """Escalating-microsleep ceiling for shm waits, in microseconds
    (floor 10).  Default 200 us: long enough to stop a yield storm from
    starving the producer, short enough that a ring hop's wake-up
    latency stays well under the kernel's socket wake path (under the
    old single-core 1 ms ceiling shm lost to TCP on one host)."""
    return max(10, get_int(SHM_SLEEP_US, 200))


def wire_crc() -> bool:
    """True when the recovery ladder (CRC trailers, NACK retransmit,
    reconnect, shm->TCP failover) is armed.  Default off = the seed's
    exact framing and thread census."""
    return get_bool(WIRE_CRC, False)


def hop_retries() -> int:
    """Per-link NACK-retransmit budget before the ladder declares the
    link corrupt and escalates; floor 0 (= first corruption escalates)."""
    return max(0, get_int(HOP_RETRIES, 8))


def reconnect_timeout_s() -> float:
    """Re-dial/re-accept budget for one dropped data socket; past it
    the ladder escalates to the gang abort."""
    return max(0.1, get_float(RECONNECT_TIMEOUT_S, 20.0))


def ladder_retain() -> int:
    """Retained sent frames per link (the replay buffer); floor 2."""
    return max(2, get_int(LADDER_RETAIN, 32))


def collective_timeout_s() -> float:
    """Per-collective deadline in seconds; 0 (default) = no deadline,
    the seed's block-forever behavior."""
    return max(0.0, get_float(COLLECTIVE_TIMEOUT, 0.0))


def ctrl_fanout() -> int:
    """Children per sub-coordinator in the hierarchical control tree;
    0 (default) = every same-host rank.  Overflow children attach
    directly to the root."""
    return max(0, get_int(CTRL_FANOUT, 0))


def ctrl_tree_on() -> bool:
    """Hierarchical control tree kill-switch (HVD_CTRL_TREE, default
    on).  Mixed-engine gangs must turn it off: the tree tags are
    Python-engine-only, and a native parent cannot fold its host."""
    return get_bool(CTRL_TREE, True)


def quorum_on() -> bool:
    """Elastic re-form majority gate (HVD_QUORUM, default on): re-form
    only when a strict majority of the last-committed membership is
    reachable; a minority self-terminates instead of split-braining."""
    return get_bool(QUORUM, True)


def serve_port() -> int:
    """Rank-0 serving front-door port; 0 (default) binds ephemeral."""
    return max(0, get_int(SERVE_PORT, 0))


def serve_max_batch() -> int:
    """Continuous-batching decode slots; floor 1."""
    return max(1, get_int(SERVE_MAX_BATCH, 8))


def serve_max_queue() -> int:
    """Admission queue bound (beyond it, /generate sheds with a 503);
    floor 1."""
    return max(1, get_int(SERVE_MAX_QUEUE, 64))


def trace_enabled() -> bool:
    """True when gang-wide tracing is on: every rank streams spans."""
    return get_bool(TRACE, False)


def trace_dir() -> str:
    """Directory for the per-rank ``trace_rank{R}.jsonl`` span files."""
    return get_str(TRACE_DIR, ".") or "."


def trace_clock_sync_cycles() -> int:
    """Worker clock-ping cadence in background cycles (floor 1); the
    first ping goes out on the first cycle regardless."""
    return max(1, get_int(TRACE_CLOCK_SYNC_CYCLES, 200))


def blackbox_enabled() -> bool:
    """True unless HVD_BLACKBOX=0: the flight recorder is always-on."""
    return get_bool(BLACKBOX, True)


def blackbox_events() -> int:
    """Ring capacity in events (floor 16 — a dump with fewer events than
    one collective's worth of context is not evidence)."""
    return max(16, get_int(BLACKBOX_EVENTS, 512))


def blackbox_dir() -> str:
    """Directory the per-rank ``blackbox_rank<r>.json`` dumps land in."""
    return get_str(BLACKBOX_DIR, "hvd_blackbox") or "hvd_blackbox"


def agg_interval_s() -> float:
    """Gang-aggregation fold cadence on rank 0; floor 0.05 s."""
    return max(0.05, get_float(AGG_INTERVAL, 2.0))


def alert_ewma_alpha() -> float:
    """EWMA smoothing factor for the trailing baselines, clamped to
    (0, 1].  Higher = baseline chases recent folds faster."""
    return min(1.0, max(0.01, get_float(ALERT_EWMA_ALPHA, 0.3)))


def alert_warmup() -> int:
    """Folds a rule's baseline must observe before it may fire; floor 1
    (a rule with no baseline at all has nothing to compare against)."""
    return max(1, get_int(ALERT_WARMUP, 3))


def alert_collapse_frac() -> float:
    """throughput_collapse threshold: fire when the gang collective rate
    drops below this fraction of its EWMA baseline; clamped to (0, 1)."""
    return min(0.99, max(0.01, get_float(ALERT_COLLAPSE_FRAC, 0.5)))


def alert_skew_factor() -> float:
    """straggler_skew growth multiple vs a rank's EWMA baseline;
    floor 1.0."""
    return max(1.0, get_float(ALERT_SKEW_FACTOR, 3.0))


def alert_skew_floor_ms() -> float:
    """Absolute straggler-skew floor in milliseconds — growth below it
    never fires (small-number noise)."""
    return max(0.0, get_float(ALERT_SKEW_FLOOR_MS, 50.0))


def alert_queue_factor() -> float:
    """queue_growth multiple vs the EWMA queue-depth baseline;
    floor 1.0."""
    return max(1.0, get_float(ALERT_QUEUE_FACTOR, 3.0))


def alert_retry_factor() -> float:
    """retry_spike multiple vs the EWMA per-fold retry-count baseline;
    floor 1.0."""
    return max(1.0, get_float(ALERT_RETRY_FACTOR, 3.0))


def alert_serve_p99_ms() -> float:
    """serve_p99_breach ceiling for the interval's gang-wide decode-step
    p99, in milliseconds; 0 (default) disables the rule."""
    return max(0.0, get_float(ALERT_SERVE_P99_MS, 0.0))


def send_wait_cap_s() -> float:
    """Hard cap on any single PeerSender.wait, always on (a dead sender
    thread must never hang a hop silently).  Generous by design: it is
    a backstop, not a tunable deadline — use HVD_COLLECTIVE_TIMEOUT for
    bounded-time collectives."""
    return get_float(SEND_WAIT_CAP_S, 300.0)
