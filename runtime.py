"""Pipeline-parallel inference runtime CLI.

Parity with /root/reference/runtime.py (the main application, 605-730),
re-architected for a single-controller JAX/TPU world:

- The reference launches one OS process per rank (`runtime.py RANK WORLDSIZE`)
  and wires them with gloo TCP or TensorPipe RPC. Here ONE controller process
  drives all chips: `rank` must be 0 and `worldsize` becomes the number of
  pipeline stages (devices). There is no network bring-up, no wire protocol,
  and no command plane — the schedule broadcast (CMD_SCHED) and stop
  (CMD_STOP) of the reference (runtime.py:404-452) are plain function calls.
- `--comm spmd` compiles the whole pipeline into one XLA program with
  ppermute edges (block-aligned partitions); `--comm host` drives per-stage
  jit programs with device_put edges and supports arbitrary sublayer cuts
  and runtime-adaptive quantization. `p2p`/`rpc` are accepted as aliases
  for host mode (their capability equivalent).
- Schedule resolution precedence is identical (runtime.py:291-355): manual
  `-pt` partition > single-stage degenerate > native sched-pipeline.
- Monitoring keys, window adaptation via env ADAPTIVE_QUANT /
  SEND_CONSTRAINT / WINDOW_SIZE, result accuracy vs labels or softmax
  confidence (runtime.py:236-257) are preserved.
"""
import argparse
import logging
import os
import queue
import sys
import threading
import time
from typing import List, Optional, Tuple

import numpy as np

import monitoring
from pipeedge_tpu import telemetry
from pipeedge_tpu.comm import CMD_ADMIT, CMD_DEAD, CMD_SCHED, CMD_STOP
from pipeedge_tpu.health import guard as nan_guard
from pipeedge_tpu.telemetry import flight
from pipeedge_tpu.telemetry import metrics as prom
from pipeedge_tpu.models import get_microbatch_size, registry
from pipeedge_tpu.parallel import pipeline as host_pipeline
from pipeedge_tpu.parallel import spmd
from pipeedge_tpu.sched.scheduler import sched_pipeline
from pipeedge_tpu.utils import data as data_utils
from pipeedge_tpu.utils import quant as quantutil
from pipeedge_tpu.utils import report_device_memory, report_devices
from pipeedge_tpu.utils.threads import ThreadSafeCounter, make_lock

logger = logging.getLogger(__name__)

# Env knobs (reference runtime.py:40-52)
ENV_WINDOW_SIZE = "WINDOW_SIZE"
ENV_SEND_CONSTRAINT = "SEND_CONSTRAINT"
ENV_ADAPTIVE_QUANT = "ADAPTIVE_QUANT"
ADAPTIVE_QUANT_HEURISTIC = "HEURISTIC"
ADAPTIVE_QUANT_HEURISTIC2 = "HEURISTIC2"
ADAPTIVE_QUANT_CONTROLLER = "CONTROLLER"

MONITORING_KEY_MODEL = 'shard'
MONITORING_KEY_OUTPUT = 'output'
MONITORING_KEY_QUANT_ENCODE = 'quant_encode'
MONITORING_KEY_QUANT_DECODE = 'quant_decode'
MONITORING_KEY_SEND = 'send'
MONITORING_KEY_RECV = 'recv'
# liveness plane: one beat per received DCN heartbeat frame (accuracy
# column = sender rank), so the post-mortem CSV shows exactly when each
# peer's beats stopped
MONITORING_KEY_LIVENESS = 'liveness'
# heartbeat RTT: one row per completed beat round trip (work = rtt ms,
# accuracy column = peer rank) — beats prove liveness, these prove the
# command plane is still FAST; the monitoring snapshot and hb_rtt.csv
# carry the same series /metrics exports as pipeedge_heartbeat_rtt_ms
MONITORING_KEY_HB_RTT = 'hb_rtt'

results_counter = ThreadSafeCounter(name="runtime.results")
label_queue = queue.Queue()
# multi-process (dcn) command state (reference runtime.py:400-415)
stop_event = threading.Event()
sched_q = queue.Queue()
# why the fleet stopped: a CMD_STOP carrying a rank id means that rank died
# mid-run (peer-death protocol, beyond the reference's acknowledged
# non-fault-tolerance at rpc/__init__.py:83-86); None = clean stop
stop_info: List[Optional[int]] = [None]
# cumulative CMD_STOP count: round r of a multi-schedule run ends at the
# (r+1)-th stop, so a stop that lands while a worker is still tearing down
# the previous round is counted, not lost (stop_event alone would race)
stop_counter = ThreadSafeCounter(name="runtime.stops")
# set once the fleet is tearing down cleanly (empty CMD_SCHED sent/received):
# from then on, dropped connections are expected, not peer deaths
fleet_shutdown = threading.Event()
# failover mode state (--on-peer-death failover): ranks announced dead via
# CMD_DEAD or observed locally; deaths accumulate for the whole run
dead_ranks: set = set()
dead_lock = make_lock("runtime.dead")
# rejoined-but-not-healed ranks (guarded by dead_lock): alive spare
# capacity that must NOT silently reclaim its old stage at the next
# round's failover re-plan. --on-peer-rejoin spare keeps ranks here;
# heal clears the bench at the round boundary that restores capacity.
benched_ranks: set = set()
# gray-quarantined ranks (guarded by dead_lock): alive but benched by
# the peer-health plane (--on-peer-degraded quarantine) because their
# EWMA degradation score confirmed a straggler. Kept SEPARATE from
# benched_ranks so a rejoin heal clearing the bench can never silently
# readmit a quarantined straggler; only probation readmission
# (pipeedge_tpu/health/scorer.py) removes entries here.
quarantined_ranks: set = set()
# capacity-benched ranks (guarded by dead_lock): alive ranks the
# capacity controller (--autoscale-ranks, serving/autoscale.py) parked
# as spares because the pipeline is over-provisioned. Kept SEPARATE
# from benched_ranks/quarantined_ranks so a rejoin heal or a health
# readmission can never silently re-seat a capacity decision; only the
# controller's own scale-up (plan_rejoin onto idle survivors) removes
# entries here.
autoscaled_ranks: set = set()
# a death landed mid-round: the data rank ends the round, re-schedules over
# the survivors, and replays the unacknowledged microbatches
failover_event = threading.Event()
# elastic membership (--on-peer-rejoin): a confirmed-dead rank passed the
# JOIN admission handshake and is live again. The handler removes it from
# dead_ranks; `_heal_state` carries what the data rank's round loop needs
# to close the capacity loop at the next boundary (docs/FAULT_TOLERANCE.md
# rank lifecycle: alive -> grace -> dead -> rejoining -> spare/healed).
_heal_state: dict = {
    "detect_ns": None,    # first death detection of the open episode
    "rejoin_ns": None,    # admission stamp of the most recent rejoin
    "pre_failure": None,  # schedule running when the episode's death hit
    "pending": False,     # a heal should be attempted at the boundary
}
# optional result capture (--save-results): handle_results appends every
# delivered output here so runs can be compared bit-for-bit
_results_sink: Optional[list] = None
# failover telemetry: monotonic_ns stamps of each death detection, consumed
# by the data rank's recovery span (detection -> replay-round completion)
_failover_detect_ns: List[int] = []

# /metrics plane (pipeedge_tpu/telemetry/metrics.py): the DCN transport
# hooks feed these; tools/serve.py renders the same registry
_WIRE_BYTES = prom.REGISTRY.counter(
    "pipeedge_edge_wire_bytes_total",
    "bytes moved over DCN pipeline edges, by direction and peer rank")
_EDGE_BITS = prom.REGISTRY.gauge(
    "pipeedge_edge_bits",
    "negotiated wire bitwidth per DCN edge (0 = uncompressed)")
_EDGE_PATH = prom.REGISTRY.gauge(
    "pipeedge_edge_path",
    "negotiated transport tier per DCN edge "
    "(0 = socket_v2, 1 = zerocopy, 2 = local hand-off)")
_LEDGER_SNAPSHOTS = prom.REGISTRY.counter(
    "pipeedge_ledger_snapshots_total",
    "microbatch-ledger snapshots taken (bounds failover replay state)")
_HEARTBEATS_RX = prom.REGISTRY.counter(
    "pipeedge_heartbeats_received_total",
    "liveness-plane heartbeat frames received, by sender rank")
_FAILOVER_EVENTS = prom.REGISTRY.counter(
    "pipeedge_failover_events_total",
    "mid-run peer deaths entering the failover path")
_PEER_DEATHS = prom.REGISTRY.counter(
    "pipeedge_peer_deaths_total", "peer deaths observed (any mode)")
_REBALANCE_EVENTS = prom.REGISTRY.counter(
    "pipeedge_rebalance_events_total",
    "accepted telemetry-driven partition rebalances (--rebalance auto)")
_REJOINS = prom.REGISTRY.counter(
    "pipeedge_rejoins_total",
    "peers re-admitted through the JOIN handshake after a confirmed death")
_TTFC = prom.REGISTRY.gauge(
    "pipeedge_time_to_full_capacity_seconds",
    "latest heal episode: first death detection -> partition healed back "
    "to full capacity at a round boundary")
# gray-failure plane (docs/FAULT_TOLERANCE.md): the heartbeat RTT
# percentiles the peer-health scorer reads (q = p50 | p99). The frame-
# integrity counter (pipeedge_frames_corrupt_total) lives with its
# verification site in comm/dcn.py (`dcn.FRAMES_CORRUPT`).
_HB_RTT = prom.REGISTRY.gauge(
    "pipeedge_heartbeat_rtt_ms",
    "heartbeat round-trip percentiles per peer over the bounded sample "
    "window (q = p50 | p99)")


def _declare_fleet_metric_labels(world_size: int, rank: int) -> None:
    """Pre-declare the per-peer label matrices (pipelint PL501): the
    fleet's membership fixes every (direction, peer) series up front, so
    scrapers see the full matrix at 0 instead of series appearing at
    first increment."""
    for r in range(world_size):
        if r == rank:
            continue
        _HEARTBEATS_RX.declare(src=str(r))
        _PEER_DEATHS.declare(peer=str(r))
        _REJOINS.declare(peer=str(r))
        for key in (MONITORING_KEY_SEND, MONITORING_KEY_RECV):
            _WIRE_BYTES.declare(direction=key, peer=str(r))


def handle_cmd(cmd: int, tensors: Tuple) -> None:
    """Process a command (reference runtime.py:404-415)."""
    if cmd == CMD_STOP:
        logger.info("handle_cmd: stop")
        if tensors:
            stop_info[0] = int(np.asarray(tensors[0]))
            monitoring.flush()   # post-mortem CSVs must survive the abort
        stop_counter.add(1)
        stop_event.set()
    elif cmd == CMD_SCHED:
        logger.info("handle_cmd: sched")
        # pair the schedule with the stop count at its ARRIVAL (commands
        # from the data rank ride one connection, so this round's stop is
        # guaranteed not yet counted): the worker's round ends at base+1.
        # Relative counting is what lets a REJOINED worker — who missed
        # every earlier round's stop — fall straight into the sequence.
        sched_q.put((stop_counter.value, tensors))
    elif cmd == CMD_ADMIT:
        # the admission ack: purely informational on the worker — its
        # next CMD_SCHED carries everything it needs (global round index,
        # stop baseline); the log line is the operator's confirmation
        rnd_now = int(np.asarray(tensors[0])) if tensors else -1
        logger.warning("handle_cmd: re-admitted into the fleet "
                       "(current round %d)", rnd_now)
    elif cmd == CMD_DEAD:
        dead = int(np.asarray(tensors[0]))
        logger.warning("handle_cmd: rank %d announced dead (failover)", dead)
        with dead_lock:
            known = dead in dead_ranks
            dead_ranks.add(dead)
        if not known:
            # every survivor may broadcast the same death; count the EVENT
            # once and stamp detection once, or the failover metrics/spans
            # multiply by the fleet size
            _record_failover_detect(dead)
        failover_event.set()
        monitoring.flush()
    else:
        logger.warning("handle_cmd: Unknown command: %s", cmd)


def _record_failover_detect(dead: int, failover: bool = True) -> None:
    """First-observation bookkeeping for a peer death: one detect span,
    one detection stamp (the recovery span's start), one death count —
    callers dedupe against dead_ranks (or stop_info) before calling.
    `failover=False` (abort path) skips the failover-event counter."""
    now = time.monotonic_ns()
    telemetry.record("failover", "detect", now, now)
    flight.note("peer_death", dead_rank=dead, failover=failover)
    _failover_detect_ns.append(now)
    if _heal_state["detect_ns"] is None:
        # anchor of the time-to-full-capacity clock: the FIRST detection
        # of the episode a later heal closes
        _heal_state["detect_ns"] = now
    _PEER_DEATHS.inc(peer=str(dead))
    if failover:
        _FAILOVER_EVENTS.inc()


def get_window_size() -> int:
    """Window period for monitoring/adaptation (reference runtime.py:40-44)."""
    return int(os.getenv(ENV_WINDOW_SIZE, "10"))


def handle_results(tensors) -> None:
    """Process result tensors (reference runtime.py:236-257): accuracy from
    labels when available (FIFO order guaranteed here), else softmax
    confidence."""
    outputs = np.asarray(tensors)
    n_items = get_microbatch_size(outputs, verify=True)
    # class labels only apply to [B, n_classes] outputs; per-token logits
    # (causal LMs, [B, S, vocab]) fall back to softmax confidence. Pop the
    # label queue either way so it stays in sync with the microbatch stream.
    ubatch_labels = None if label_queue.empty() else label_queue.get()
    if ubatch_labels is not None and outputs.ndim == 2:
        assert len(outputs) == len(ubatch_labels)
        pred = outputs.argmax(axis=-1)
        acc = int((pred == np.asarray(ubatch_labels)).sum())
    else:
        exp = np.exp(outputs - outputs.max(axis=-1, keepdims=True))
        probs = exp / exp.sum(axis=-1, keepdims=True)
        conf = probs.max(axis=-1)   # [B] or [B, S]
        acc = float(conf.reshape(conf.shape[0], -1).mean(axis=1).sum())
    monitoring.iteration(MONITORING_KEY_OUTPUT, work=n_items, accuracy=acc,
                         safe=False)
    logger.debug("outputs is %s", outputs)
    if _results_sink is not None:
        _results_sink.append(outputs)
    results_counter.add(n_items)


def parse_yaml_sched(sched: List[dict], hosts: Optional[List[str]]) -> \
        Tuple[List[Tuple[int, int]], List[int]]:
    """Parse the scheduler's YAML into stage_layers + stage_ranks
    (reference runtime.py:260-288). Ranks here are device indices."""
    assert isinstance(sched, list)
    if len(sched) == 0:
        raise RuntimeError("No viable schedule found")
    stage_layers = []
    stage_ranks = []
    # numeric host names round-trip through YAML as ints
    hosts_s = [str(h) for h in hosts] if hosts else None
    for stage in sched:
        assert len(stage) == 1
        for host, layers in stage.items():
            assert len(layers) == 2
            stage_layers.append((int(layers[0]), int(layers[1])))
            if hosts_s:
                try:
                    stage_ranks.append(hosts_s.index(str(host)))
                except ValueError:
                    logger.error("Scheduling: host not in hosts list: %s", host)
                    raise
            else:
                try:
                    stage_ranks.append(int(host))
                except ValueError:
                    logger.error("Scheduling: 'hosts' not specified, failed "
                                 "to parse as device index: %s", host)
                    raise
    return stage_layers, stage_ranks


def get_pipeline_sched(world_size: int, hosts: Optional[List[str]],
                       partition: Optional[List[Tuple[int, int]]],
                       quant: Optional[List[int]],
                       rank_order: Optional[List[int]], model_name: str,
                       microbatch_size: int, s_models_file: Optional[str],
                       s_dev_types_file: Optional[str],
                       s_dev_file: Optional[str],
                       dtype: str = 'float32') -> \
        Tuple[List[Tuple[int, int]], List[int], List[int]]:
    """Schedule resolution: manual partition > single-stage degenerate >
    native scheduler (reference runtime.py:291-355)."""
    if partition:
        logger.info("Scheduling: using user-defined partitioning")
        # reject out-of-range/non-contiguous -pt up front: an oversized
        # partition otherwise marks an interior stage is_last (its r ==
        # model total), whose classifier logits then feed the next stage
        # and fail with an unrelated broadcast error deep in layer_norm
        from pipeedge_tpu.parallel.decode import validate_partition
        total = registry.get_model_layers(model_name)
        try:
            validate_partition(partition, total)
        except ValueError as exc:
            raise RuntimeError(
                f"-pt: {exc} ({model_name} has {total} sublayers)") from exc
        stage_layers = partition
        stage_quant = quant if quant else [0] * len(stage_layers)
        stage_ranks = rank_order if rank_order else list(range(len(stage_layers)))
    elif quant:
        raise RuntimeError("Must specify partition with quantization")
    elif rank_order:
        raise RuntimeError("Must specify partition with rank stage ordering")
    elif world_size <= 1:
        logger.info("Scheduling: single-node execution (degenerate case)")
        stage_layers = [(1, registry.get_model_layers(model_name))]
        stage_quant = [0]
        stage_ranks = [0]
    else:
        logger.info("Scheduling: using scheduler algorithm")
        if hosts and len(hosts) != world_size:
            raise RuntimeError("Specified hosts count != world size")
        # dtype must match the profile records' dtype key (the scheduler
        # selects the model profile by exact (dtype, batch_size) match,
        # native/sched_pipeline_main.cpp:135) — chip profiles are bfloat16
        sched = sched_pipeline(model_name, 2, 2, microbatch_size,
                               dtype=dtype,
                               models_file=s_models_file,
                               dev_types_file=s_dev_types_file,
                               dev_file=s_dev_file)
        stage_layers, stage_ranks = parse_yaml_sched(sched, hosts)
        stage_quant = [0] * len(stage_layers)
    logger.info("Scheduling: stage-to-layer mapping: %s", stage_layers)
    logger.info("Scheduling: stage output quantization: %s", stage_quant)
    logger.info("Scheduling: stage-to-device mapping: %s", stage_ranks)
    return stage_layers, stage_quant, stage_ranks


def load_dataset(dataset_cfg: dict, model_name: str, batch_size: int,
                 ubatch_size: int):
    """Load inputs based on model (reference runtime.py:358-401); synthetic
    data replaces network-fetched samples under zero egress."""
    cfg = registry.get_model_config(model_name)
    name = dataset_cfg['name']
    root = dataset_cfg['root']
    split = dataset_cfg['split']
    indices = dataset_cfg['indices']
    shuffle = dataset_cfg['shuffle']
    if name == 'CoLA':
        try:
            from transformers import AutoTokenizer
            tokenizer = AutoTokenizer.from_pretrained(model_name)
            dataset = data_utils.load_dataset_glue(tokenizer, 'cola', split,
                                                   ubatch_size)
            dataset = data_utils.load_dataset_subset(
                dataset, indices=indices, max_size=batch_size, shuffle=shuffle)
        except Exception as exc:
            logger.warning("CoLA unavailable offline (%s); using synthetic "
                           "token data", exc)
            dataset = data_utils.synthetic_token_dataset(
                batch_size, seq_len=64, vocab_size=cfg.vocab_size or 30522,
                n_labels=max(cfg.num_labels, 2))
    elif name == 'ImageNet':
        try:
            from transformers import AutoImageProcessor
            extractor = AutoImageProcessor.from_pretrained(model_name)
            dataset = data_utils.load_dataset_imagenet(extractor, root or
                                                       'ImageNet', split=split)
            dataset = data_utils.load_dataset_subset(
                dataset, indices=indices, max_size=batch_size, shuffle=shuffle)
        except Exception as exc:
            logger.warning("ImageNet unavailable (%s); using synthetic images",
                           exc)
            dataset = data_utils.synthetic_image_dataset(
                batch_size, shape=(cfg.num_channels, cfg.image_size,
                                   cfg.image_size),
                n_labels=max(cfg.num_labels, 2))
    elif cfg.vocab_size:  # token models: BERT and GPT-2
        dataset = data_utils.synthetic_token_dataset(
            batch_size, seq_len=min(64, cfg.max_position_embeddings or 64),
            vocab_size=cfg.vocab_size, n_labels=max(cfg.num_labels, 2))
    else:
        dataset = data_utils.synthetic_image_dataset(
            batch_size, shape=(cfg.num_channels, cfg.image_size, cfg.image_size),
            n_labels=max(cfg.num_labels, 2))
    return dataset


def _make_adaptive_callback(edge_stages, window_size: int, edge_keys=None):
    """Window-period bitwidth adaptation (reference runtime.py:121-216).

    `edge_stages` are the stages whose *output* edge is adaptive (i.e. all but
    the final stage); each must expose a mutable `quant_bit`. `edge_keys[i]`
    names the monitoring key carrying stage i's edge telemetry (wire Mbits
    per microbatch) — per-edge windows, so each stage adapts on its OWN
    edge's measured traffic, exactly as each reference rank reads its own
    local 'send' window (reference runtime.py:123-127). Default: every stage
    reads MONITORING_KEY_SEND (the per-process key — correct for a DCN rank,
    which owns exactly one edge).
    """
    policy = os.getenv(ENV_ADAPTIVE_QUANT)
    if not policy:
        return None
    if edge_keys is None:
        edge_keys = [MONITORING_KEY_SEND] * len(edge_stages)
    rate_constraint = float(os.getenv(ENV_SEND_CONSTRAINT, "0"))
    controllers = {}
    ctl_state = {}

    def callback(i: int, out) -> None:
        tag = i + 1
        if tag % window_size != 0:
            # controller policy counts down its bitwidth1 window split
            if policy == ADAPTIVE_QUANT_CONTROLLER:
                for stage in edge_stages:
                    st = ctl_state.get(id(stage))
                    if st:
                        bw1, bw2, it1 = st
                        stage.quant_bit = (bw1 if it1 > 0 else bw2) % max(
                            quantutil.BITWIDTHS)
                        ctl_state[id(stage)] = (bw1, bw2, max(0, it1 - 1))
            return
        out_arr = np.asarray(out[0] if isinstance(out, tuple) else out)
        ubatch_size = get_microbatch_size(out_arr)
        for stage_idx, stage in enumerate(edge_stages):
            key = edge_keys[stage_idx]
            with monitoring.get_locked_context(key) as mctx:
                if mctx is None:
                    return
                window_perf = mctx.get_window_perf(key=key)
                window_work = mctx.get_window_work(key=key)
                heartrate = mctx.get_window_heartrate(key=key)
            if policy == ADAPTIVE_QUANT_HEURISTIC:
                # discrete compress-ratio ladder (runtime.py:121-154)
                if rate_constraint > 0:
                    target_time = ubatch_size * window_size / rate_constraint
                else:
                    target_time = float('inf')
                target_datasize = target_time * max(window_perf, 1e-12)
                qbit = stage.quant_bit
                eff = window_work * (32 / qbit if qbit > 0 else 1)
                ratio = int(eff / target_datasize) + 1 if target_datasize > 0 else 1
                for bound, bit in ((1, 0), (2, 16), (4, 8), (5, 6), (8, 4)):
                    if ratio <= bound:
                        stage.quant_bit = bit
                        break
                else:
                    stage.quant_bit = 2
            elif policy == ADAPTIVE_QUANT_HEURISTIC2:
                # analytic largest-feasible bitwidth (runtime.py:156-174)
                if rate_constraint <= 0:
                    continue
                ubatch_time = ubatch_size / rate_constraint
                src_bit = 32
                qbit = quantutil.constrain_max_bitwidth(
                    ubatch_time, max(window_work, 1e-12) / window_size,
                    max(window_perf, 1e-12), src_bit)
                stage.quant_bit = max(2, qbit) % src_bit
            elif policy == ADAPTIVE_QUANT_CONTROLLER:
                # Kalman/integral controller window split (runtime.py:177-216)
                if id(stage) not in controllers:
                    bw_start = stage.quant_bit or max(quantutil.BITWIDTHS)
                    controllers[id(stage)] = \
                        quantutil.AdaptiveBitwidthPerformanceController(
                            rate_constraint, quantutil.BITWIDTHS, bw_start)
                ctl = controllers[id(stage)]
                ctl.reference = rate_constraint
                send_rate = heartrate * ubatch_size
                bw1, bw2, it1 = ctl(send_rate, window_size)
                ctl_state[id(stage)] = (bw1, bw2, it1)
                stage.quant_bit = (bw1 if it1 > 0 else bw2) % max(
                    quantutil.BITWIDTHS)
            logger.info("Adaptive quantization (%s): bitwidth=%d", policy,
                        stage.quant_bit)

    return callback


class _EdgeQuantState:
    """Mutable output-edge bitwidth for a DCN rank — the role of the
    reference's non-persistent `quant_bit` module buffer that adaptive hooks
    mutate (reference runtime.py:464-467, 143-153)."""

    def __init__(self, quant_bit: int):
        self.quant_bit = quant_bit


def _register_dcn_monitor_hooks(ctx) -> None:
    """Wire send/recv transport hooks to the monitoring keys, measuring the
    actual bytes and transfer time of every pipeline-edge frame on this rank
    (reference p2p:132-152 + runtime.py:219-230).

    Feed-channel frames (raw inputs from the data rank to the head stage)
    are excluded: the reference injects inputs locally (enqueue_tensor), so
    its 'send' telemetry — the adaptive policies' sensor — never contains
    feed bytes. A colocated data rank + stage would otherwise pollute the
    stage's edge window with uncompressed feed traffic."""
    from pipeedge_tpu.comm import dcn

    def make_hooks(key):
        def pre(peer, channel):
            if dcn.base_channel(channel) != dcn.CHANNEL_FEED:
                monitoring.iteration_start(key)

        def post(peer, channel, tensors):
            if dcn.base_channel(channel) == dcn.CHANNEL_FEED:
                return
            if tensors is None:  # transfer aborted mid-frame
                monitoring.iteration_abort(key)
                return
            nbytes = sum(int(t.nbytes) for t in tensors)
            _WIRE_BYTES.inc(nbytes, direction=key, peer=str(peer))
            monitoring.iteration(key, work=nbytes * 8 / 1e6)

        return pre, post

    ctx.register_send_hooks(*make_hooks(MONITORING_KEY_SEND))
    ctx.register_recv_hooks(*make_hooks(MONITORING_KEY_RECV))


def run_pipeline_host(args, stage_layers, stage_quant, stage_ranks,
                      ubatches, labels) -> None:
    """Host-driven pipeline (arbitrary cut points, adaptive quantization)."""
    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    dtype = jnp.bfloat16 if args.dtype == 'bfloat16' else jnp.float32
    pipe = host_pipeline.build_pipeline(
        args.model_name, stage_layers, model_file=args.model_file,
        devices=[devices[r % len(devices)] for r in stage_ranks],
        quant_bits=stage_quant, dtype=dtype)
    window_size = get_window_size()
    # Per-edge telemetry: one monitoring key per inter-stage edge, fed with
    # that edge's actual wire bytes each microbatch (the per-rank 'send' key
    # of the reference, p2p:132-152 + runtime.py:219-230 — qualified by
    # stage index since one controller process owns every edge). The plain
    # 'send' key aggregates all edges per microbatch.
    edge_keys = [f"{MONITORING_KEY_SEND}{i}"
                 for i in range(len(pipe.stages) - 1)]
    for key in edge_keys:
        monitoring.add_key(key, work_type='Mbits')
    adaptive = _make_adaptive_callback(pipe.stages[:-1], window_size,
                                       edge_keys=edge_keys)

    for lb in labels:
        label_queue.put(lb)

    def on_edge_bytes(i, edge_bytes):
        total_mbits = 0.0
        for key, nbytes in zip(edge_keys, edge_bytes):
            mbits = nbytes * 8 / 1e6
            total_mbits += mbits
            monitoring.iteration(key, work=mbits, safe=False)
        monitoring.iteration(MONITORING_KEY_SEND, work=total_mbits, safe=False)

    def on_result(i, out):
        handle_results(out)
        if adaptive is not None:
            adaptive(i, out)

    pipe.edge_bytes_callback = on_edge_bytes
    pipe.ubatch_callback = on_result
    inputs = [jnp.asarray(u, dtype=dtype if u.dtype.kind == 'f' else None)
              for u in ubatches]
    # --measure-rounds: round 0 pays the XLA compiles (the reference's
    # single-shot methodology, runtime.py:493-505 there); later rounds
    # measure the warm pipeline. Same data each round, so label-driven
    # accuracy is unchanged; per-round lines let callers record both.
    # --rebalance auto: between rounds, re-split the batch to the
    # microbatch size the MEASURED steady-state cadence says minimizes the
    # fill/drain-vs-overhead latency model (parallel/pipeline.py
    # plan_microbatches), instead of keeping the CLI --ubatch forever.
    rounds = max(1, args.measure_rounds)
    adaptive_mb = args.rebalance == "auto" and rounds > 1
    stats = {}
    for rnd in range(rounds):
        if rnd:
            for lb in labels:
                label_queue.put(lb)
        tik = time.monotonic()
        t_span0 = time.monotonic_ns()
        # request-tagged dispatch/retire spans (single-controller
        # analogue of the DCN feed's per-microbatch trace contexts)
        traces = ([telemetry.TraceContext(f"r{rnd}.mb{i}", "batch",
                                          parent="host.run")
                   for i in range(len(inputs))]
                  if telemetry.enabled() else None)
        _, stats = pipe.run(inputs, traces=traces)
        tok = time.monotonic()
        # round track: mb ids restart each measure round; the segmenting
        # consumers (report/flows) key on these intervals
        telemetry.record("runtime", f"round{rnd}", t_span0,
                         time.monotonic_ns())
        if rounds > 1:
            batch_total = sum(len(u) for u in inputs)
            steady = stats.get("steady_state_throughput_items_sec")
            print(f"round={rnd} latency_sec={tok - tik:.6f} "
                  f"throughput_items_sec={batch_total / (tok - tik):.3f}")
            if steady:
                # own line, steady-first: both the round= and latency_sec=
                # line formats are parsed by tooling/tests
                print(f"steady_state_throughput_items_sec={steady:.3f} "
                      f"round={rnd}")
        if adaptive_mb and rnd + 1 < rounds:
            # growth bound: the user sized --ubatch for the device's
            # memory; the planner may merge up to 4x that (activations
            # grow linearly with u) but never balloon to the whole batch
            inputs, labels = _adapt_microbatches(
                pipe, stats, inputs, labels,
                max_ubatch=4 * args.ubatch_size)
    _report(tik, tok, inputs)
    report_device_memory()      # while the stages' weights are still placed
    steady = stats.get("steady_state_throughput_items_sec")
    if steady:
        # warm cadence without the first (compile-tainted) microbatch —
        # what rebalance decisions and benches should chase, next to the
        # end-to-end number _report prints
        print(f"steady_state_throughput_items_sec={steady:.3f}")


def _adapt_microbatches(pipe, stats, inputs, labels,
                        max_ubatch: Optional[int] = None):
    """One adaptive-microbatching step between host-driver measure rounds:
    decompose this round's measured steady per-microbatch interval into
    per-item time vs per-microbatch fixed overhead, ask `plan_microbatches`
    for the latency-minimizing split, and re-slice the batch (inputs AND
    labels, same boundaries, so FIFO label/result pairing holds). The next
    round pays one re-compile for the new shape — that is what measure
    rounds are for."""
    import jax.numpy as jnp

    interval = stats.get("steady_mb_interval_s")
    if not interval or not inputs:
        return inputs, labels
    u_cur = max(len(u) for u in inputs)
    t_fixed = stats.get("host_dispatch_s_per_ubatch") or 0.0
    t_item = max(0.0, interval - t_fixed) / u_cur
    batch_total = sum(len(u) for u in inputs)
    u_new, m_new, t_pred = host_pipeline.plan_microbatches(
        batch_total, len(pipe.stages), t_item, t_fixed,
        max_ubatch=max(max_ubatch or 0, u_cur) or None)
    if u_new == u_cur:
        return inputs, labels
    logger.info("adaptive ubatch: %d -> %d items/microbatch (%d -> %d "
                "microbatches; modeled round latency %.4fs)", u_cur, u_new,
                len(inputs), m_new, t_pred)
    print(f"adaptive_ubatch={u_new} microbatches={m_new} "
          f"predicted_latency_sec={t_pred:.6f}")
    flat = jnp.concatenate(list(inputs), axis=0)
    new_inputs = [flat[i:i + u_new] for i in range(0, batch_total, u_new)]
    new_labels = labels
    if labels and all(lb is not None for lb in labels):
        lflat = np.concatenate([np.asarray(lb) for lb in labels], axis=0)
        new_labels = [lflat[i:i + u_new]
                      for i in range(0, batch_total, u_new)]
    # window follows the split: enough in-flight microbatches to cover the
    # pipeline depth, but never more than double buffering provides
    pipe.max_inflight = max(len(pipe.stages) + 1,
                            min(2 * len(pipe.stages), m_new))
    return new_inputs, new_labels


def run_pipeline_spmd(args, stage_layers, stage_quant, stage_ranks,
                      ubatches, labels) -> None:
    """SPMD pipeline: one XLA program, ppermute edges (block-aligned)."""
    import jax
    import jax.numpy as jnp

    entry = registry.get_model_entry(args.model_name)
    dtype = jnp.bfloat16 if args.dtype == 'bfloat16' else jnp.float32
    total = registry.get_model_layers(args.model_name)
    # conflict check on the RAW argument, before any (potentially multi-GB)
    # stage weights load
    if stage_ranks and list(stage_ranks) != list(range(len(stage_layers))) \
            and (args.spmd_dp > 1 or args.spmd_tp > 1 or args.spmd_sp > 1):
        raise RuntimeError("-r stage ranks cannot combine with "
                           "--spmd-dp/--spmd-tp/--spmd-sp mesh axes")
    if args.spmd_tp > 1 and args.spmd_sp > 1:
        raise RuntimeError("--spmd-tp and --spmd-sp are mutually exclusive "
                           "(Megatron TP assumes a full local sequence)")
    need = len(stage_layers) * args.spmd_dp * max(args.spmd_tp, args.spmd_sp)
    have = len(jax.devices())
    if need > have:
        raise RuntimeError(
            f"mesh needs {need} devices (stages x dp x tp|sp = "
            f"{len(stage_layers)} x {args.spmd_dp} x "
            f"{max(args.spmd_tp, args.spmd_sp)}) but only {have} available")
    stage_params = []
    for i, (l, r) in enumerate(stage_layers):
        # stacked block layout required: the SPMD driver pads and re-stacks
        # per-stage blocks across the 'stage' mesh axis
        _, params, _ = registry.module_shard_factory(
            args.model_name, args.model_file, l, r, stage=i, dtype=dtype,
            unroll=False)
        stage_params.append(params)
    n_stages = len(stage_layers)
    ranks = None
    if stage_ranks and list(stage_ranks) != list(range(n_stages)):
        devices = jax.devices()
        mapped = [r % len(devices) for r in stage_ranks]
        if len(set(mapped)) != n_stages:
            # hard error, not a silent identity fallback: the user asked
            # for an explicit stage placement the mesh cannot honor
            raise RuntimeError(
                f"-r stage ranks {list(stage_ranks)} map to non-distinct "
                f"devices {mapped} on {len(devices)} available devices; "
                "spmd mode needs one distinct device per stage (drop -r "
                "for the default identity order)")
        ranks = mapped
    mesh = spmd.make_pipeline_mesh(n_stages, dp=args.spmd_dp,
                                   tp=args.spmd_tp, sp=args.spmd_sp,
                                   stage_ranks=ranks)
    from pipeedge_tpu.ops import qcollectives
    qcollectives.reset_trace_tally()
    pipe = spmd.build_spmd_pipeline(entry.family.FAMILY, entry.config,
                                    stage_layers, stage_params, mesh,
                                    quant_bit=list(stage_quant) if stage_quant
                                    else 0, sp_kind=args.spmd_sp_kind)
    # the loaders put every stage on the first device; the mesh now holds
    # the placed copies, so let the staging ones go (a whole second model
    # on that chip otherwise, for as long as the pipeline runs)
    del stage_params
    for lb in labels:
        label_queue.put(lb)
    inputs = jnp.asarray(np.stack(ubatches),
                         dtype=dtype if ubatches[0].dtype.kind == 'f' else None)
    pipe.run(inputs)  # compile + warmup
    tik = time.monotonic()
    outputs = np.asarray(pipe.run(inputs))
    tok = time.monotonic()
    for out in outputs:
        handle_results(out)
    _report(tik, tok, ubatches)
    report_device_memory()      # while the stages' weights are still placed
    if args.tp_quant_bits:
        # fold the traced quantized-collective sites into telemetry +
        # /metrics: each site inside the tick scan executes ~ticks x
        # blocks-per-stage times per run (bubble ticks included — they
        # move wire bits too); 2 runs (warmup + timed). stage=None: the
        # whole pipeline is ONE XLA program here, so the record is an
        # all-stage aggregate — per-stage attribution comes from the dcn
        # --stage-tp path, where each worker folds its own tally
        blocks_per_stage = max((r - l + 1) // 4 for l, r in stage_layers)
        ticks = pipe.n_ticks(len(ubatches))
        summary = qcollectives.record_collectives(
            executions=2 * ticks * max(1, blocks_per_stage))
        logger.info("quantized collectives (--tp-quant-bits %d): %s",
                    args.tp_quant_bits, summary)


# Host-side quantized wire codec: moved to the library
# (pipeedge_tpu/comm/wire.py) so the DCN decode mode shares it; aliased here
# for the runtime call sites and existing tests.
from pipeedge_tpu.comm.wire import (WireCorruptError,
                                    crc_enabled as _wire_crc_enabled,
                                    wire_decode as _wire_decode,
                                    wire_encode as _wire_encode,
                                    wire_encode_device as _wire_encode_device)


ENV_LEDGER_SNAPSHOT = "DCN_LEDGER_SNAPSHOT"  # acks between ledger
# snapshots (0 disables). Each snapshot compacts acknowledged microbatch
# payloads out of the ledger and advances the replay frontier, so a
# failover replays from the last snapshot's frontier — O(unacknowledged)
# work and memory — instead of rescanning (and holding) the whole round.
DEFAULT_LEDGER_SNAPSHOT = 8


class _MicrobatchLedger:
    """Bounded in-flight ledger for the data rank (failover mode): every
    microbatch is registered with its id before dispatch, acknowledged when
    its result frame lands, and REPLAYED (same id) after a failover if no
    acknowledgment arrived. Duplicate results — a replay overlapping a
    frame that was already in flight when the stage died, or a transient
    resend — are dropped by id, and delivery to `handle_results` is held
    until contiguous, so the result stream at the data rank is exactly-once
    and in microbatch order regardless of arrival order.

    Snapshots (`maybe_snapshot`, every `snapshot_every` acks) keep the
    failover replay O(in-flight) instead of O(round): acknowledged
    payloads are dropped (they can never be refed — an ack is final) and
    the replay frontier advances past the acked prefix, so `pending()`
    after a mid-round death scans and ships only the microbatches that
    genuinely need replaying from the last snapshot on."""

    def __init__(self, ubatches, labels, snapshot_every: Optional[int] = None):
        self._ubatches = list(ubatches)
        self._labels = (list(labels) if labels
                        else [None] * len(self._ubatches))
        self._snapshot_every = (snapshot_every if snapshot_every is not None
                                else int(os.getenv(
                                    ENV_LEDGER_SNAPSHOT,
                                    str(DEFAULT_LEDGER_SNAPSHOT))))
        self._acks_since_snapshot = 0
        self._frontier = 0        # lowest possibly-unacked microbatch id
        self.snapshots = 0        # snapshots taken (tests/metrics)
        # mbid -> epoch of the incarnation whose result was accepted: the
        # dedupe key carries the epoch, so forensics (and tests) can tell
        # a same-incarnation resend from a stale-incarnation replay
        self._acked: dict = {}
        self._held: dict = {}       # acked but not yet contiguous
        self._next_deliver = 0
        # per-source epoch floor (fence_rank): an ack produced by an
        # incarnation below the floor is stale and refused — the transport
        # already fences these at the reader; this is the ledger's own
        # belt-and-braces (a stale frame must NEVER ack a microbatch)
        self._epoch_floor: dict = {}
        self.stale_dropped = 0
        # request <-> microbatch mapping (docs/OBSERVABILITY.md request
        # tracing): the feed loop records each microbatch's trace/request
        # id here, so a postmortem bundle and trace_report --request can
        # resolve a request to its microbatches (and back) after the fact
        self._traces: dict = {}
        self._lock = make_lock("runtime.ledger")
        self.done = threading.Event()
        if not self._ubatches:
            self.done.set()

    def record_trace(self, mbid: int, rid: str) -> None:
        """Bind microbatch `mbid` to request id `rid` (feed time)."""
        with self._lock:
            self._traces[int(mbid)] = str(rid)

    def trace_of(self, mbid: int) -> Optional[str]:
        with self._lock:
            return self._traces.get(int(mbid))

    def forensics(self) -> dict:
        """The ledger slice of a failover postmortem bundle: progress,
        the replay set, and the request ids in flight when it was taken
        (ids only — payloads stay out of the bundle)."""
        with self._lock:
            pending = [i for i in range(self._frontier,
                                        len(self._ubatches))
                       if i not in self._acked]
            return {"microbatches": len(self._ubatches),
                    "acked": len(self._acked),
                    "pending_mbids": pending,
                    "frontier": self._frontier,
                    "snapshots": self.snapshots,
                    "stale_dropped": self.stale_dropped,
                    "next_deliver": self._next_deliver,
                    "traces": {str(k): v
                               for k, v in sorted(self._traces.items())}}

    @property
    def acked_count(self) -> int:
        with self._lock:
            return len(self._acked)

    def pending(self) -> List[Tuple[int, np.ndarray]]:
        """(microbatch id, ubatch) pairs not yet acknowledged — what the
        feed loop sends, and after a failover, exactly the replay set.
        The scan starts at the snapshot frontier: everything below it was
        acked (and compacted away) by the last snapshot."""
        with self._lock:
            return [(i, self._ubatches[i])
                    for i in range(self._frontier, len(self._ubatches))
                    if i not in self._acked]

    def maybe_snapshot(self) -> bool:
        """Count an ack toward the snapshot cadence; snapshot when due.
        Called by the results loop after every accepted ack (cheap: a
        counter bump between snapshots)."""
        if self._snapshot_every <= 0:
            return False
        with self._lock:
            self._acks_since_snapshot += 1
            if self._acks_since_snapshot < self._snapshot_every:
                return False
            self._snapshot_locked()
        _LEDGER_SNAPSHOTS.inc()
        return True

    def snapshot(self) -> None:
        """Compact now (see `maybe_snapshot` for the periodic form)."""
        with self._lock:
            self._snapshot_locked()
        _LEDGER_SNAPSHOTS.inc()

    def _snapshot_locked(self) -> None:
        # an acked payload is never refed (acks are final even across
        # failovers — replay covers only unacked ids), so drop it and
        # advance the frontier past the acked prefix: replay work and
        # ledger memory both become O(unacknowledged since snapshot)
        for i in range(self._frontier, len(self._ubatches)):
            if i in self._acked:
                self._ubatches[i] = None
        while self._frontier < len(self._ubatches) \
                and self._frontier in self._acked:
            self._frontier += 1
        self._acks_since_snapshot = 0
        self.snapshots += 1

    def acked_epochs(self) -> dict:
        """mbid -> producing incarnation's epoch, for every accepted ack."""
        with self._lock:
            return dict(self._acked)

    def fence_rank(self, src: int, min_epoch: int) -> None:
        """Refuse acks from `src` incarnations below `min_epoch` (mirrors
        the transport fence, `DistDcnContext.min_epoch_of`)."""
        with self._lock:
            self._epoch_floor[src] = max(self._epoch_floor.get(src, 0),
                                         int(min_epoch))

    def ack(self, mbid: int, out: np.ndarray, epoch: int = 0,
            src: Optional[int] = None) -> bool:
        """Acknowledge microbatch `mbid`'s result; False for a duplicate
        or a stale-incarnation ack (both dropped). Results are surfaced
        through `handle_results` in id order so the label queue and
        accuracy bookkeeping stay aligned."""
        deliver = []
        with self._lock:
            if src is not None and epoch < self._epoch_floor.get(src, 0):
                self.stale_dropped += 1
                return False
            if mbid in self._acked or not 0 <= mbid < len(self._ubatches):
                return False
            self._acked[mbid] = int(epoch)
            self._held[mbid] = out
            while self._next_deliver in self._held:
                i = self._next_deliver
                deliver.append((self._labels[i], self._held.pop(i)))
                self._next_deliver += 1
            complete = len(self._acked) == len(self._ubatches)
        for label, result in deliver:
            if label is not None:
                label_queue.put(label)
            handle_results(result)
        if complete:
            self.done.set()
        return True


def _collect_fleet_digests(ctx, args, stage_ranks):
    """Pull every stage rank's CUMULATIVE span digest over the command
    channel once (kilobytes; comm/dcn.py `collect_digest`). Collected
    ONCE per round boundary and shared by every consumer — the
    rebalancer and the peer-health scorer each difference the same
    cumulative snapshot against their own baselines, so two features
    never pay two serial fleet sweeps (up to N x 10 s each on exactly
    the degraded links the health plane targets). Returns
    `{rank: digest}`, or None when any rank is dead/unreachable (the
    whole window is unmeasurable — partial snapshots must not advance
    anyone's baseline)."""
    with dead_lock:
        gone = set(dead_ranks)
    out = {}
    for src in sorted(set(stage_ranks)):
        if src == args.rank:
            rec = telemetry.recorder()
            out[src] = rec.digest() if rec is not None else {}
        elif src in gone:
            logger.info("telemetry window: rank %d is dead; skipping "
                        "this round", src)
            return None
        else:
            try:
                out[src] = ctx.collect_digest(src, timeout=10.0)
            except Exception as exc:  # noqa: BLE001 - any peer hiccup
                logger.warning("telemetry window: digest collection from "
                               "rank %d failed (%s)", src, exc)
                return None
    return out


def _estimates_from_digests(cur_digests, sched, prev_digests: dict,
                            min_samples: int = 2):
    """One consumer's measured window: difference a fleet digest
    snapshot against `prev_digests` (the CALLER-owned baselines, which
    advance here — every rank's, atomically, so windows always cover one
    time span) and decompose into per-stage service estimates
    (telemetry/feedback.py). Returns the estimates dict, or None when
    the snapshot is absent or fails the self-test."""
    from pipeedge_tpu.telemetry import feedback

    if cur_digests is None:
        return None
    stage_layers = sched[0]
    windows = [feedback.diff_digests(cur, prev_digests.get(src, {}))
               for src, cur in cur_digests.items()]
    prev_digests.update(cur_digests)
    est = feedback.stage_estimates(feedback.merge_digests(windows))
    problems = feedback.check_estimates(est, len(stage_layers),
                                        min_samples=min_samples)
    if problems:
        logger.info("telemetry window failed the self-test (%s)",
                    "; ".join(problems))
        return None
    return est


def _consider_rebalance(ctx, args, policy, sched, prev_digests: dict,
                        rnd: int, cur_digests=None):
    """One closed-loop decision at a round boundary (data rank only):
    measure this round's window (from the boundary's shared digest
    snapshot `cur_digests`, collected by `_collect_fleet_digests`) and
    ask the policy (sched/rebalance.py) whether re-solving the partition
    with the MEASURED profile is worth a re-schedule. Returns the
    accepted Proposal or None; never raises — an unmeasurable round
    (dead peer, incomplete estimates) keeps the running partition."""
    stage_layers, _stage_quant, _stage_ranks = sched
    t0 = time.monotonic_ns()
    # cur_digests=None means the boundary's one shared sweep already
    # failed — do NOT sweep again (the failure was fleet-wide)
    est = _estimates_from_digests(cur_digests, sched, prev_digests)
    if est is None:
        logger.info("rebalance: no measurable window; keeping partition")
        return None
    proposal = policy.consider(list(stage_layers), est, rnd)
    now = time.monotonic_ns()
    telemetry.record("rebalance", "plan", t0, now)
    if proposal is None:
        return None
    # instant marker per ACCEPTED re-partition: trace_report's
    # `rebalance_events` (the zero-churn assertion) counts these
    telemetry.record("rebalance", "apply", now, now)
    _REBALANCE_EVENTS.inc()
    logger.warning("rebalance: round %d partition %s -> %s (predicted "
                   "bottleneck %.4fs -> %.4fs, gain %.1f%%)", rnd,
                   list(stage_layers), proposal.partition,
                   proposal.bottleneck_before_s,
                   proposal.bottleneck_after_s, 100 * proposal.gain)
    # machine-parseable line (tests/test_rebalance.py greps this)
    print(f"rebalance_round={rnd} "
          f"partition={','.join(f'{l},{r}' for l, r in proposal.partition)} "
          f"predicted_gain={proposal.gain:.4f}")
    return proposal


def _consider_peer_health(ctx, args, hstate: dict, sched, next_sched,
                          world_size: int, rnd: int,
                          cur_digests=None) -> None:
    """One gray-failure decision at a round boundary (data rank only,
    docs/FAULT_TOLERANCE.md gray failures): fold this round's measured
    signals — per-stage service time vs the fleet median (the same
    digest windows the rebalancer reads), heartbeat RTT p99 vs the fleet
    median (comm/dcn.py `heartbeat_rtt_stats`), transport redial counts
    — into the EWMA health scorer, and act on its transitions:

    - suspect / floor-hold / recovery: recorded (health spans, flight
      ring) but nothing moves.
    - quarantine (`--on-peer-degraded quarantine`, confirmed over N
      windows, min-fleet floor verified by DRY-RUNNING the next round's
      failover plan with the victim benched): a PLANNED bench — the rank
      is alive and this round fully drained, so adding it to
      `quarantined_ranks` makes the next boundary's re-plan move its
      stage to a spare with no ledger replay.
    - probation readmit: the score recovered (heartbeat RTT is the main
      signal a benched rank still emits); un-benching lets the next
      round's own schedule restore the stage through the same re-plan
      path — and one bad probation window re-quarantines without
      re-confirmation.

    Never raises; an unmeasurable service window still folds RTT/retry
    signals so quarantined ranks keep moving toward (or away from)
    readmission."""
    from pipeedge_tpu import health as health_mod

    scorer = hstate["scorer"]
    _stage_layers, _q, stage_ranks = sched
    # cur_digests=None = the boundary's shared sweep failed: no service
    # signal this window, but RTT/retry signals still fold below
    est = _estimates_from_digests(cur_digests, sched,
                                  hstate["prev_digests"])

    # TRUE median (statistics.median: middle-two mean for even counts):
    # an upper median would make a 2-stage fleet's straggler its own
    # baseline (ratio 1.0 — detector blind)
    from statistics import median

    # relative signals: a fleet where everything is slow is balanced,
    # not gray — normalize against the fleet median. Absolute floors
    # guard the false-positive side: a stage a few ms over the median
    # (natural skew) or a sub-5 ms loopback RTT at 2x the median (pure
    # noise) reads as HEALTHY (ratio 1.0 — an actively decaying signal,
    # not a missing one). Env-tunable for unusual fleets.
    excess_floor_s = float(os.getenv("PIPEEDGE_HEALTH_MIN_EXCESS_S",
                                     "0.02"))
    rtt_floor_ms = float(os.getenv("PIPEEDGE_HEALTH_RTT_FLOOR_MS", "5"))
    service_ratio: dict = {}
    if est:
        svc = {stage_ranks[i]: e.service_s for i, e in est.items()
               if 0 <= i < len(stage_ranks)}
        med = median(svc.values()) if svc else 0.0
        if med > 0:
            service_ratio = {
                r: (s / med if s - med >= excess_floor_s else 1.0)
                for r, s in svc.items()}
    rtt = ctx.heartbeat_rtt_stats()
    rtt_ratio: dict = {}
    if rtt:
        med = median(v["p99_ms"] for v in rtt.values())
        for peer, v in rtt.items():
            _HB_RTT.set(v["p50_ms"], peer=str(peer), q="p50")
            _HB_RTT.set(v["p99_ms"], peer=str(peer), q="p99")
            if med > 0 and len(rtt) > 1:
                rtt_ratio[peer] = (v["p99_ms"] / med
                                   if v["p99_ms"] >= rtt_floor_ms
                                   else 1.0)
    retries_now = ctx.send_retry_counts()
    prev_r = hstate["prev_retries"]
    window_retries = {p: n - prev_r.get(p, 0)
                      for p, n in retries_now.items()}
    hstate["prev_retries"] = retries_now

    with dead_lock:
        dead_now = set(dead_ranks)
        bench_now = (set(benched_ranks) | set(quarantined_ranks)
                     | set(autoscaled_ranks))
    # score every rank carrying a stage this round PLUS every
    # quarantined rank (still beating — its RTT drives readmission)
    for peer in sorted((set(stage_ranks) | set(quarantined_ranks))
                       - dead_now - {args.rank}):
        sample = health_mod.HealthSample(
            service_ratio=service_ratio.get(peer),
            rtt_ratio=rtt_ratio.get(peer),
            send_retries=int(window_retries.get(peer, 0)))
        floor_ok = False
        if args.on_peer_degraded == "quarantine" \
                and scorer.state_of(peer) in (health_mod.STATE_SUSPECT,
                                              health_mod.STATE_PROBATION):
            # min-fleet floor: quarantine (or a probation RELAPSE —
            # also a quarantine decision) only if the NEXT round still
            # has a runnable plan with this rank ALSO benched — the same
            # failover cascade the boundary re-plan will actually run
            planned = _plan_failover(args, next_sched, world_size,
                                     dead_now,
                                     benched=bench_now | {peer})
            floor_ok = planned is not None
        t = scorer.observe(peer, sample, can_quarantine=floor_ok)
        if t is None:
            continue
        now = time.monotonic_ns()
        if t.to == health_mod.STATE_QUARANTINED:
            with dead_lock:
                quarantined_ranks.add(peer)
            telemetry.record("health", f"quarantine:r{peer}", now, now)
            flight.note("peer_degraded", rank=peer, to=t.to,
                        score=round(t.score, 4), reason=t.reason)
            flight.maybe_dump("gray", context={
                "rank": peer, "round": rnd, "score": t.score,
                "reason": t.reason,
                "health": scorer.snapshot()})
            logger.warning("peer health: QUARANTINING rank %d at round "
                           "%d (%s); its stage moves to a spare at the "
                           "next boundary", peer, rnd, t.reason)
            # machine-parseable line (tools/chaos_dcn.py + CI gate)
            print(f"quarantine_rank={peer} round={rnd} "
                  f"score={t.score:.4f}", flush=True)
        elif t.frm == health_mod.STATE_QUARANTINED \
                and t.to == health_mod.STATE_PROBATION:
            with dead_lock:
                quarantined_ranks.discard(peer)
            telemetry.record("health", f"readmit:r{peer}", now, now)
            flight.note("peer_readmitted", rank=peer,
                        score=round(t.score, 4))
            logger.warning("peer health: READMITTING rank %d on "
                           "probation at round %d (%s)", peer, rnd,
                           t.reason)
            print(f"readmit_rank={peer} round={rnd} "
                  f"score={t.score:.4f}", flush=True)
        elif t.frm == t.to:
            # floor hold (suspect stays suspect / probation relapse
            # held): checked BEFORE the suspect branch, which would
            # otherwise swallow a suspect-state hold as a second
            # `suspect` span and keep gray.held at zero
            telemetry.record("health", f"held:r{peer}", now, now)
            flight.note("peer_quarantine_held", rank=peer,
                        score=round(t.score, 4))
        elif t.to == health_mod.STATE_SUSPECT:
            telemetry.record("health", f"suspect:r{peer}", now, now)
            flight.note("peer_suspect", rank=peer,
                        score=round(t.score, 4), reason=t.reason)
        else:                 # suspect/probation -> healthy
            telemetry.record("health", f"recovered:r{peer}", now, now)
            flight.note("peer_recovered", rank=peer,
                        score=round(t.score, 4))


def _plan_failover(args, sched, world_size: int, dead_now: set,
                   benched: Optional[set] = None):
    """Re-schedule over the survivors (sched/failover.py cascade). The
    native scheduler re-solve is attempted only when profile files were
    given; spare substitution — which preserves the partition and thus
    bit-identical replay — is the fallback. None = no capacity: abort.
    `benched` ranks (rejoined, not healed) keep no stage but stay in the
    spare pool at lowest priority."""
    from pipeedge_tpu.sched import failover as failover_sched

    scheduler_fn = None
    if args.sched_models_file or args.sched_dev_types_file \
            or args.sched_dev_file:
        def scheduler_fn(n_survivors):
            return get_pipeline_sched(
                n_survivors, None, None, None, None, args.model_name,
                args.ubatch_size, args.sched_models_file,
                args.sched_dev_types_file, args.sched_dev_file,
                dtype=args.dtype)
    return failover_sched.plan_failover(*sched, world_size, dead_now,
                                        scheduler_fn=scheduler_fn,
                                        benched=benched)


def _consider_autoscale(ctx, args, a_state: dict, sched, schedules,
                        sched_idx: int, world_size: int, rnd: int,
                        cur_digests=None) -> None:
    """One capacity decision at a round boundary (data rank only): the
    pipeline-level half of the closed capacity loop (--autoscale-ranks;
    the decision engine is serving/autoscale.py's CapacityController —
    confirm/dwell hysteresis, flap damper, dry-run `held`, identical to
    the router's replica loop). Capacity unit = pipeline stages.

    Signal: the boundary's shared digest window (the same sweep the
    rebalancer and health scorer read) decomposed into per-stage
    service estimates — up pressure when the bottleneck stage's
    per-microbatch service time crosses `--autoscale-rank-high`
    (adding a stage lets the re-cut shed layers off the critical
    path), down pressure below `--autoscale-rank-low` (the pipeline is
    over-provisioned; merging stages trades idle bubbles for none).

    Actuation through EXISTING machinery only:
    - scale-up = planned rejoin: `plan_rejoin(sched, None, ...)`
      expands onto idle survivors — including capacity-benched
      spares — and is written over the remaining rounds, exactly like
      `_maybe_heal`'s re-expansion path.
    - scale-down = planned contraction: the span is re-solved over one
      FEWER stage and the victim (the rank carrying the fewest layers,
      never the data rank) is dropped from the placement and joins
      `autoscaled_ranks`, keeping it benched through later failover
      re-plans and available to scale-up's re-expansion — the
      contraction is built here first, so an un-runnable one renders
      as a visible `held` decision instead of an abort."""
    from pipeedge_tpu.sched import failover as failover_sched
    from pipeedge_tpu.sched import rebalance
    from pipeedge_tpu.serving import autoscale as autoscale_mod

    est = _estimates_from_digests(cur_digests, sched,
                                  a_state["prev_digests"])
    with dead_lock:
        dead_now = set(dead_ranks)
    # state BEFORE the lazy controller construction: the controller
    # probes size_fn() at __init__, and every closure below reads
    # a_state at call time
    a_state.update(sched=sched, schedules=schedules,
                   sched_idx=sched_idx, dead=dead_now, last_apply=None)

    if a_state.get("controller") is None:
        max_size = (min(args.autoscale_max, world_size)
                    if args.autoscale_max else world_size)

        def _classify(pol, sig):
            b = sig.get("bottleneck_s")
            if b is None:
                return 0     # unmeasurable window: streaks reset
            if b >= args.autoscale_rank_high:
                return 1
            if b <= args.autoscale_rank_low:
                return -1
            return 0

        def _plan(direction, cur, target):
            sched_now = a_state["sched"]
            dead_now = a_state["dead"]
            if direction == "up":
                planned = failover_sched.plan_rejoin(
                    sched_now, None, world_size, dead_now,
                    align=4 if args.stage_tp > 1 else 1)
                if planned is None:
                    return {"ok": False,
                            "reason": "no idle survivor to expand onto"}
                return {"ok": True, "planned": planned}
            # scale-down = partition CONTRACTION (the inverse of the up
            # path's re-expansion): merge the span over target stages
            # and drop the victim from the placement. Benching through
            # the failover cascade is NOT enough — on a full pipeline
            # substitute_spares hands the stage back to the benched
            # rank as the last-resort spare (a visible no-op).
            stage_layers, _q, stage_ranks = sched_now
            candidates = [(hi - lo + 1, i)
                          for i, (lo, hi) in enumerate(stage_layers)
                          if stage_ranks[i] != args.rank
                          and stage_ranks[i] not in dead_now]
            if not candidates:
                return {"ok": False,
                        "reason": "no benchable stage (data rank "
                                  "holds the only one)"}
            _, idx = min(candidates)
            victim = stage_ranks[idx]
            try:
                contracted, _ = rebalance.solve_partition(
                    [1.0] * stage_layers[-1][1], target,
                    align=4 if args.stage_tp > 1 else 1)
            except ValueError as exc:
                return {"ok": False,
                        "reason": f"contraction to {target} stage(s) "
                                  f"unsolvable: {exc}"}
            new_ranks = [r for r in stage_ranks if r != victim]
            if len(new_ranks) != target:
                return {"ok": False,
                        "reason": f"placement mismatch: {len(new_ranks)} "
                                  f"survivors for {target} stage(s)"}
            return {"ok": True, "victim": victim,
                    "planned": (list(contracted), [0] * target,
                                new_ranks)}

        def _apply(plan):
            scheds = a_state["schedules"]
            idx_now = a_state["sched_idx"]
            planned = plan["planned"]
            for j in range(idx_now + 1, len(scheds)):
                scheds[j] = (list(planned[0]), list(planned[1]),
                             list(planned[2]))
            if "victim" not in plan:                    # scale-up
                with dead_lock:
                    for r_new in planned[2]:
                        autoscaled_ranks.discard(r_new)
                a_state["last_apply"] = ("up", planned[2])
            else:                                       # scale-down
                victim = plan["victim"]
                with dead_lock:
                    autoscaled_ranks.add(victim)
                a_state["last_apply"] = ("down", victim)

        a_state["controller"] = autoscale_mod.CapacityController(
            autoscale_mod.CapacityPolicy(
                min_size=args.autoscale_min,
                max_size=max(max_size, args.autoscale_min),
                confirm=args.autoscale_confirm,
                cooldown_s=args.autoscale_cooldown),
            mode=args.autoscale_ranks,
            size_fn=lambda: len(a_state["sched"][0]),
            plan_fn=_plan, apply_fn=_apply,
            classify_fn=_classify, label="stages")

    stage_layers = sched[0]
    signals = {"size": len(stage_layers), "brownout_level": 0}
    if est:
        svc = [e.service_s for e in est.values()]
        bott = max(svc)
        signals["bottleneck_s"] = bott
        # classic steady-state pipeline bubble ratio: how much of the
        # fleet's stage-seconds are spent waiting on the bottleneck
        signals["bubble_frac"] = (1.0 - sum(svc) / (len(svc) * bott)
                                  if bott > 0 else 0.0)
    d = a_state["controller"].tick(signals)
    if d is None:
        return
    # machine-parseable decision line (tools/chaos_dcn.py / CI grep)
    print(f"{d.line()} round={rnd}", flush=True)
    applied = a_state["last_apply"]
    if applied is None:
        return
    kind, detail = applied
    if kind == "up":
        print(f"autoscale_rank direction=up round={rnd} "
              f"ranks={','.join(str(r) for r in detail)}", flush=True)
    else:
        print(f"autoscale_rank direction=down round={rnd} "
              f"victim={detail}", flush=True)


def run_pipeline_dcn(args, schedules, ubatches, labels) -> None:
    """Multi-process pipeline over the DCN transport: this process is ONE
    rank (reference `runtime.py RANK WORLDSIZE` semantics, run_pipeline_p2p
    418-511). Rank `--data-rank` resolves/broadcasts the schedule, streams
    microbatches to the first stage, and collects results from the last.

    `schedules` is a list of (stage_layers, stage_quant, stage_ranks)
    rounds: after each round completes (CMD_STOP), the data rank broadcasts
    the next round's CMD_SCHED and the live fleet rebuilds its stages — the
    re-scheduling path the reference designed (CMD_SCHED lands on sched_q,
    runtime.py:404-415) but never shipped (its runtime consumes exactly one
    schedule at startup). An EMPTY CMD_SCHED means "no more rounds": workers
    exit their schedule loop."""
    import jax.numpy as jnp

    from pipeedge_tpu.comm import chaos, dcn

    rank, world_size = args.rank, args.worldsize
    _declare_fleet_metric_labels(world_size, rank)
    # per-rank flight recorder: always-on event ring; postmortem bundles
    # fire on failover (data rank) — one per cooldown window
    flight.configure(rank=rank)
    data_rank = args.data_rank
    failover_mode = args.on_peer_death == "failover"
    addrs = dcn.parse_rank_addrs(args.dcn_addrs, world_size, args.port)
    dtype = jnp.bfloat16 if args.dtype == 'bfloat16' else jnp.float32

    with dcn.DistDcnContext(world_size, rank, addrs,
                            cmd_handler=handle_cmd,
                            accept_joins=args.on_peer_rejoin != "ignore"
                            ) as ctx:
        _register_dcn_monitor_hooks(ctx)
        chaos.maybe_install(ctx)   # deterministic fault injection, env-gated
        if ctx.send_retries > 0 and not failover_mode:
            # a resent frame can DUPLICATE or reorder a microbatch; only
            # the failover ledger dedupes by id. Without it, the FIFO
            # label/result pairing can silently misalign.
            logger.warning(
                "DCN_SEND_RETRIES=%d without --on-peer-death failover: "
                "resends are not deduplicated; result/label alignment is "
                "not guaranteed after a transient fault", ctx.send_retries)

        def on_peer_death(dead: int) -> None:
            if stop_info[0] is not None:
                return  # the fleet is already aborting for a known death
            # Grace window: connections also drop during the clean fleet
            # teardown (empty CMD_SCHED), which may still be in flight on
            # another socket — wait briefly for it before declaring a
            # failure. Mid-run or between rounds, connections never drop
            # cleanly, so anything else is a death.
            if fleet_shutdown.wait(timeout=2.0):
                return
            monitoring.flush()   # the beat CSVs are about to matter
            if failover_mode and dead != data_rank:
                with dead_lock:
                    announced = dead in dead_ranks
                    dead_ranks.add(dead)
                if announced:
                    return
                _record_failover_detect(dead)
                logger.error("rank %d: peer rank %d died; entering failover",
                             rank, dead)
                failover_event.set()
                # every rank may detect independently; the announcement is
                # idempotent at the receivers (dead_ranks is a set) and the
                # data rank alone orchestrates the recovery
                try:
                    ctx.cmd_broadcast(CMD_DEAD,
                                      [np.asarray(dead, np.int32)],
                                      best_effort=True)
                except OSError:  # pragma: no cover - best_effort guards
                    pass
                return
            # the DATA rank's death is never survivable — it alone holds
            # the ledger, the inputs, and the orchestration — so even in
            # failover mode it takes the abort path below
            _record_failover_detect(dead, failover=False)
            logger.error("rank %d: peer rank %d died; stopping the pipeline",
                         rank, dead)
            stop_info[0] = dead
            # broadcast BEFORE waking local waiters: the data rank's finally
            # block broadcasts a plain CMD_STOP once stop_event fires, and
            # the death-carrying stop must reach peers first
            try:
                ctx.cmd_broadcast(CMD_STOP, [np.asarray(dead, np.int32)],
                                  best_effort=True)
            except OSError:  # pragma: no cover - best_effort already guards
                pass
            stop_event.set()

        ctx.register_peer_death_handler(on_peer_death)

        # heal cascade state shared between the rejoin handler (reader-
        # thread dispatch) and the data rank's round loop
        round_state = {"rnd": 0}

        def on_peer_rejoin(src: int, epoch: int) -> None:
            """A peer passed the JOIN admission handshake: pull it out of
            the terminal dead set (it is live idle-spare capacity again),
            and — on the data rank — ack the admission (CMD_ADMIT) and arm
            the heal for the next round boundary."""
            with dead_lock:
                was_dead = src in dead_ranks
                dead_ranks.discard(src)
                # the rejoiner is live idle capacity, but its old stage
                # stays where the failover moved it until a heal says
                # otherwise (spare mode never says otherwise)
                if was_dead:
                    benched_ranks.add(src)
            now = time.monotonic_ns()
            telemetry.record("rejoin", "admit", now, now)
            _REJOINS.inc(peer=str(src))
            _heal_state["rejoin_ns"] = now
            if was_dead:
                _heal_state["pending"] = True
            logger.warning("rank %d: peer rank %d rejoined with epoch %d"
                           "%s", rank, src, epoch,
                           " (was confirmed dead)" if was_dead else "")
            if rank != data_rank:
                return
            # machine-parseable admission line (tools/chaos_dcn.py keys
            # its rejoin timestamp on it)
            print(f"rejoin_rank={src} epoch={epoch} "
                  f"was_dead={int(was_dead)}", flush=True)
            # epoch floor for the ledger: results signed by the fenced
            # incarnation must never ack a microbatch
            ledger = ledger_ref[0]
            if ledger is not None:
                ledger.fence_rank(src, ctx.min_epoch_of(src))
            try:
                ctx.cmd_send(src, CMD_ADMIT,
                             [np.asarray(round_state["rnd"], np.int32)],
                             timeout=10.0)
            except OSError as exc:
                logger.warning("CMD_ADMIT to rank %d failed (%s); it "
                               "will learn from the next CMD_SCHED",
                               src, exc)

        ledger_ref: List[Optional[_MicrobatchLedger]] = [None]
        ctx.register_peer_rejoin_handler(on_peer_rejoin)
        # liveness plane: beat every peer, watch every peer's beats, and
        # feed each received beat into the monitoring heartbeat windows
        # (the 'liveness' CSV is the post-mortem timeline of peer health)
        def liveness_beat(src: int) -> None:
            # raw context call: CSV row + window accounting WITHOUT the
            # facade's per-beat instant log lines — world_size beats per
            # interval would bury the very lines failover forensics greps
            _HEARTBEATS_RX.inc(src=str(src))
            with monitoring.get_locked_context(MONITORING_KEY_LIVENESS) \
                    as mctx:
                if mctx is not None:
                    mctx.iteration(key=MONITORING_KEY_LIVENESS, work=1,
                                   accuracy=src)

        ctx.register_heartbeat_hook(liveness_beat)

        def rtt_sample(src: int, rtt_ms: float) -> None:
            # per-probe feed for the monitoring snapshot / hb_rtt.csv
            # (work = rtt ms, accuracy = peer rank); the p50/p99 gauge
            # aggregation happens at round boundaries in
            # _consider_peer_health from the transport's bounded window
            with monitoring.get_locked_context(MONITORING_KEY_HB_RTT) \
                    as mctx:
                if mctx is not None:
                    mctx.iteration(key=MONITORING_KEY_HB_RTT,
                                   work=rtt_ms, accuracy=src)

        ctx.register_heartbeat_rtt_hook(rtt_sample)
        ctx.start_heartbeat(
            interval=args.heartbeat_interval if args.heartbeat_interval > 0
            else None,
            miss_threshold=args.heartbeat_miss if args.heartbeat_miss > 0
            else None)
        if ctx.epoch > 0:
            # this process IS a restarted incarnation (env DCN_EPOCH,
            # e.g. chaos restart@K:MS or an orchestrator relaunch): ask
            # the fleet to re-admit it before settling in to wait for a
            # schedule
            reached = ctx.announce_join()
            logger.warning("rank %d: restarted as epoch %d; JOIN "
                           "announced to rank(s) %s", rank, ctx.epoch,
                           reached)
        results_target = [0]
        if rank == data_rank:
            # span collection runs in the finally so round end, abort, AND
            # failover all leave a merged trace (best-effort, like
            # CMD_STOP): on the clean path it runs BEFORE the empty
            # CMD_SCHED below, while every worker is still serving frames
            # closed-loop rebalancer (--rebalance auto): re-partition the
            # NEXT rounds from this round's measured per-stage timings,
            # applied through the same CMD_SCHED broadcast failover uses
            rebalancer = None
            prev_digests: dict = {}
            if args.rebalance == "auto":
                from pipeedge_tpu.sched import rebalance as rebalance_sched
                rebalancer = rebalance_sched.RebalancePolicy(
                    threshold=args.rebalance_threshold,
                    cooldown=args.rebalance_cooldown,
                    confirm=args.rebalance_confirm,
                    align=4 if args.stage_tp > 1 else 1)
            # peer-health plane (gray-failure detection): active whenever
            # the fleet records spans — the scorer reads the same digest
            # windows the rebalancer does. `--on-peer-degraded
            # quarantine` forces telemetry on (main()); with `ignore` +
            # --trace-spans the scorer still runs for observability
            # (scores, suspect spans, flight events) but never benches.
            health_state = None
            if telemetry.enabled() and world_size > 1:
                from pipeedge_tpu import health as health_mod
                h_scorer = health_mod.PeerHealthScorer(
                    [r for r in range(world_size) if r != rank],
                    policy=health_mod.HealthPolicy(
                        suspect_threshold=args.degraded_threshold,
                        readmit_threshold=args.degraded_threshold / 2,
                        confirm=args.degraded_confirm,
                        readmit=args.degraded_readmit))
                health_mod.set_scorer(h_scorer)
                health_state = {"scorer": h_scorer, "prev_digests": {},
                                "prev_retries": {}}
            # closed capacity loop, pipeline half (--autoscale-ranks):
            # the controller is built lazily at the first boundary
            # (_consider_autoscale), from the same digest windows
            a_state = None
            if getattr(args, "autoscale_ranks", "off") != "off" \
                    and world_size > 1:
                a_state = {"prev_digests": {}, "controller": None}
            schedules = [tuple(s) for s in schedules]
            try:
                rnd = 0
                fo_t0 = None   # recovery span: detection stamp, if any
                for sched_idx in range(len(schedules)):
                    stage_layers, stage_quant, stage_ranks = \
                        schedules[sched_idx]
                    sched = (stage_layers, stage_quant, stage_ranks)
                    ledger = None
                    if failover_mode:
                        # clear BEFORE snapshotting: a death landing in
                        # between is caught by the snapshot (its rank is
                        # added to dead_ranks before the event is set),
                        # and a death landing after re-sets the event and
                        # fails the round over normally — never both missed
                        failover_event.clear()
                        with dead_lock:
                            dead_now = set(dead_ranks)
                            bench_now = (set(benched_ranks)
                                         | set(quarantined_ranks)
                                         | set(autoscaled_ranks))
                        if dead_now or bench_now:
                            # a LATER schedule round may still name a rank
                            # that died earlier (or rejoined un-healed, or
                            # was gray-quarantined); remap before
                            # broadcasting
                            if _heal_state["pre_failure"] is None:
                                _heal_state["pre_failure"] = sched
                            sched = _plan_failover(args, sched, world_size,
                                                   dead_now,
                                                   benched=bench_now)
                            if sched is None:
                                _abort_no_capacity(ctx, dead_now)
                        ledger = _MicrobatchLedger(ubatches, labels)
                        ledger_ref[0] = ledger
                    while True:
                        round_state["rnd"] = rnd
                        if rnd:
                            logger.info("re-schedule: broadcasting round %d "
                                        "(partition %s)", rnd, sched[0])
                        status = _dcn_round(args, ctx, rnd, *sched, ubatches,
                                            labels, dtype, results_target,
                                            ledger=ledger)
                        rnd += 1
                        if status != "failover":
                            if fo_t0 is not None:
                                # detection -> replay-round completion: the
                                # trace_report failover breakdown; consume
                                # this episode's stamps so the next episode
                                # starts from its own first detection
                                telemetry.record("failover", "recover",
                                                 fo_t0, time.monotonic_ns())
                                fo_t0 = None
                                del _failover_detect_ns[:]
                            # ONE digest sweep per boundary, shared by
                            # the rebalancer and the peer-health scorer
                            # (each differences it against its own
                            # baseline — the digests are cumulative)
                            boundary_digests = None
                            if (rebalancer is not None
                                    or health_state is not None
                                    or a_state is not None) \
                                    and sched_idx + 1 < len(schedules):
                                boundary_digests = _collect_fleet_digests(
                                    ctx, args, sched[2])
                            if rebalancer is not None \
                                    and sched_idx + 1 < len(schedules):
                                proposal = _consider_rebalance(
                                    ctx, args, rebalancer, sched,
                                    prev_digests, rnd - 1,
                                    cur_digests=boundary_digests)
                                if proposal is not None:
                                    # re-cut the REMAINING rounds; their
                                    # quant/rank specs stand, and a death
                                    # before they run still goes through
                                    # the per-round failover re-plan above
                                    for j in range(sched_idx + 1,
                                                   len(schedules)):
                                        _, q_j, r_j = schedules[j]
                                        schedules[j] = (
                                            [tuple(p) for p in
                                             proposal.partition], q_j, r_j)
                            if health_state is not None \
                                    and sched_idx + 1 < len(schedules):
                                # gray-failure decision at the boundary:
                                # fold this round's measured signals and
                                # quarantine/readmit before the next
                                # round's re-plan (the round is fully
                                # drained — a planned bench, no replay)
                                _consider_peer_health(
                                    ctx, args, health_state, sched,
                                    schedules[sched_idx + 1], world_size,
                                    rnd - 1,
                                    cur_digests=boundary_digests)
                            if args.on_peer_rejoin == "heal" \
                                    and _heal_state["pending"] \
                                    and sched_idx + 1 < len(schedules):
                                # heal-at-round-boundary: capacity came
                                # back mid-run; restore (or re-expand)
                                # before the next round's broadcast
                                _maybe_heal(args, sched, world_size, rnd,
                                            schedules, sched_idx)
                            if a_state is not None \
                                    and sched_idx + 1 < len(schedules):
                                # capacity decision LAST: it reads the
                                # same digest window, and its scale-up
                                # rewrite must land after any heal so
                                # the remaining rounds reflect both
                                _consider_autoscale(
                                    ctx, args, a_state, sched,
                                    schedules, sched_idx, world_size,
                                    rnd - 1,
                                    cur_digests=boundary_digests)
                            break
                        if fo_t0 is None:
                            # FIRST detection of this episode (appends are
                            # deduped per dead rank)
                            fo_t0 = (_failover_detect_ns[0]
                                     if _failover_detect_ns
                                     else time.monotonic_ns())
                        # failover postmortem bundle (flight recorder):
                        # the ledger's replay set + request map and the
                        # membership state at the moment the round failed
                        # over — written before the re-plan mutates them
                        with dead_lock:
                            fo_dead = sorted(dead_ranks)
                            fo_bench = sorted(benched_ranks)
                        flight.note("failover", dead_ranks=fo_dead,
                                    round=rnd)
                        flight.maybe_dump("failover", context={
                            "round": rnd,
                            "dead_ranks": fo_dead,
                            "benched_ranks": fo_bench,
                            "ledger": (ledger.forensics()
                                       if ledger is not None else None)})
                        # clear-then-snapshot, same ordering as above
                        failover_event.clear()
                        with dead_lock:
                            dead_now = set(dead_ranks)
                            bench_now = (set(benched_ranks)
                                         | set(quarantined_ranks)
                                         | set(autoscaled_ranks))
                        if _heal_state["pre_failure"] is None:
                            # the schedule running when the episode's
                            # death hit: what --on-peer-rejoin heal
                            # restores when its ranks come back
                            _heal_state["pre_failure"] = sched
                        replay = ledger.pending()
                        with telemetry.span("failover", "reschedule"):
                            planned = _plan_failover(args, sched, world_size,
                                                     dead_now,
                                                     benched=bench_now)
                        if planned is None:
                            _abort_no_capacity(ctx, dead_now)
                        logger.warning(
                            "failover: rank(s) %s dead (benched: %s); "
                            "re-scheduling over survivors and replaying "
                            "%d unacknowledged microbatch(es)",
                            sorted(dead_now), sorted(bench_now),
                            len(replay))
                        sched = planned
            finally:
                if getattr(args, "trace_spans", None):
                    _collect_write_spans(ctx, args)
            # no more rounds: an empty schedule releases the workers.
            # fleet_shutdown first, so peers closing in response are not
            # taken for deaths.
            fleet_shutdown.set()
            with dead_lock:
                gone = set(dead_ranks)
            ctx.cmd_broadcast(CMD_SCHED, [], exclude=gone)
        else:
            rnd = 0
            while True:
                # workers block until the schedule arrives (runtime.py:447-8),
                # polling so a peer death declared meanwhile aborts promptly
                deadline = time.monotonic() + args.sched_timeout
                while True:
                    try:
                        stop_base, tensors = sched_q.get(timeout=0.5)
                        break
                    except queue.Empty:
                        if stop_info[0] is not None:
                            raise RuntimeError(
                                f"rank {rank}: pipeline aborted: rank "
                                f"{stop_info[0]} died") from None
                        if time.monotonic() >= deadline:
                            raise RuntimeError(
                                f"rank {rank}: no CMD_SCHED within "
                                f"{args.sched_timeout}s; is the data rank up "
                                "and are --dcn-addrs consistent across "
                                "ranks?") from None
                if len(tensors) == 0:
                    logger.info("rank %d: empty CMD_SCHED; shutting down",
                                rank)
                    fleet_shutdown.set()
                    break
                stage_layers = [tuple(map(int, lr)) for lr in tensors[0]]
                stage_quant = [int(q) for q in tensors[1]]
                stage_ranks = [int(r) for r in tensors[2]]
                # the schedule carries the data rank's GLOBAL round index:
                # channel round-parity must match the fleet's, not this
                # worker's local count — a rejoined worker starts counting
                # mid-sequence (older peers without the tensor: fall back
                # to the local count, correct when nothing was missed)
                if len(tensors) > 3:
                    rnd = int(np.asarray(tensors[3]).reshape(-1)[0])
                _dcn_round(args, ctx, rnd, stage_layers, stage_quant,
                           stage_ranks, [], [], dtype, results_target,
                           stop_base=stop_base)
                rnd += 1


def _collect_write_spans(ctx, args) -> None:
    """Gather every live peer's span ring over the command channel (clock-
    aligned NTP-style, dcn.collect_spans), merge with the local ring, and
    write the Perfetto-loadable trace to `--trace-spans`. Best-effort like
    CMD_STOP: an unreachable or span-less peer is skipped, never fatal —
    this runs on abort paths where peers may already be gone."""
    from pipeedge_tpu.telemetry import chrome_trace

    rec = telemetry.recorder()
    if rec is None:
        return
    merged = rec.snapshot()
    ranks_seen = 1
    dead = ctx.dead_ranks()
    for dst in range(args.worldsize):
        if dst == args.rank or dst in dead:
            continue
        try:
            spans, offset = ctx.collect_spans(dst, timeout=5.0)
        except Exception as exc:  # noqa: BLE001 - skip unreachable peers
            logger.warning("trace-spans: collection from rank %d failed "
                           "(%s); the trace will omit it", dst, exc)
            continue
        merged.extend(telemetry.align_spans(spans, offset))
        ranks_seen += 1
    chrome_trace.dump_trace(merged, args.trace_spans)
    logger.info("trace-spans: %d span(s) from %d rank(s) -> %s (load in "
                "ui.perfetto.dev; report: python tools/trace_report.py %s)",
                len(merged), ranks_seen, args.trace_spans, args.trace_spans)


def _maybe_heal(args, sched, world_size: int, rnd: int,
                schedules, sched_idx: int) -> None:
    """One heal decision at a round boundary (`--on-peer-rejoin heal`,
    data rank only): if the capacity the episode lost is restorable —
    every rank the pre-failure schedule names is alive again, or idle
    ranks allow a re-expansion (sched/failover.py `plan_rejoin`) — clear
    the bench so the next round runs the fleet at full capacity, and
    close the episode's time-to-full-capacity clock. A restore needs no
    schedule rewrite (each remaining round's own schedule replans clean
    once the bench is empty); a genuine RE-EXPANSION is written over the
    remaining rounds, since no original schedule expresses it. The heal
    line reports the schedule the next round will ACTUALLY run. A
    rejoiner that cannot restore capacity yet simply stays a spare and
    the heal stays pending for a later boundary."""
    from pipeedge_tpu.sched import failover as failover_sched

    with dead_lock:
        dead_now = set(dead_ranks)
    pre = _heal_state["pre_failure"]
    healed = failover_sched.plan_rejoin(sched, pre, world_size, dead_now,
                                        align=4 if args.stage_tp > 1 else 1)
    if healed is None:
        logger.info("heal: capacity not restorable yet (dead=%s); the "
                    "rejoined rank stays a spare", sorted(dead_now))
        return
    restored = pre is not None and healed == (list(pre[0]), list(pre[1]),
                                              list(pre[2]))
    if restored:
        # the next round's own (possibly rebalance-re-cut) schedule runs
        # clean once the bench is empty: report THAT, not the plan
        layers, _quant, ranks = schedules[sched_idx + 1]
    else:
        for j in range(sched_idx + 1, len(schedules)):
            schedules[j] = (list(healed[0]), list(healed[1]),
                            list(healed[2]))
        layers, _quant, ranks = healed
    now = time.monotonic_ns()
    t0 = _heal_state["detect_ns"] or _heal_state["rejoin_ns"] or now
    telemetry.record("rejoin", "heal", t0, now)
    ttfc = (now - t0) / 1e9
    _TTFC.set(ttfc)
    with dead_lock:
        benched_ranks.clear()
    _heal_state["pending"] = False
    _heal_state["pre_failure"] = None
    _heal_state["detect_ns"] = None
    logger.warning("heal: partition %s to full capacity for round "
                   "%d: layers=%s ranks=%s (%.3fs after detection)",
                   "restored" if restored else "re-expanded",
                   rnd, list(layers), list(ranks), ttfc)
    # machine-parseable heal line (tools/chaos_dcn.py and the CI restart
    # smoke key their healed timestamp and final partition on it)
    print(f"heal_round={rnd} "
          f"partition={','.join(f'{l},{r}' for l, r in layers)} "
          f"ranks={','.join(str(r) for r in ranks)} "
          f"time_to_full_capacity_s={ttfc:.3f}", flush=True)


def _abort_no_capacity(ctx, dead_now: set) -> None:
    """Failover found no schedule the survivors can run: fall back to the
    abort semantics, naming the dead rank fleet-wide (death-carrying
    CMD_STOP) so every worker raises instead of waiting for a schedule."""
    dead = sorted(dead_now)[0]
    stop_info[0] = dead
    monitoring.flush()
    try:
        ctx.cmd_broadcast(CMD_STOP, [np.asarray(dead, np.int32)],
                          best_effort=True)
    except OSError:  # pragma: no cover - best_effort already guards
        pass
    stop_event.set()
    raise RuntimeError(
        f"pipeline aborted: rank {dead} died and no spare capacity "
        "remains to fail over (set --on-peer-death abort to skip the "
        "re-schedule attempt)")


def _make_tp_stage(args, l, r, stage, dtype, restored):
    """Build a stage whose blocks are Megatron-TP-sharded over this rank's
    local devices (--stage-tp N): hierarchical parallelism the reference
    cannot express — pipeline over DCN across hosts, tensor parallelism over
    ICI within each host (SURVEY.md §2.4 'composes with the pipeline').

    Returns `(fn, params)` with the work_cb calling convention
    `fn(params, payload)`; the TP block params live pre-sharded in the
    closure, so `params` is empty."""
    import jax
    from jax.sharding import Mesh

    from pipeedge_tpu.parallel import tensor as tp

    n_tp = args.stage_tp
    local = jax.local_devices()
    if len(local) < n_tp:
        raise RuntimeError(f"--stage-tp {n_tp}: only {len(local)} local "
                           "devices on this rank")
    entry = registry.get_model_entry(args.model_name)
    cfg = entry.config
    if cfg.num_attention_heads % n_tp or cfg.intermediate_size % n_tp \
            or cfg.kv_heads % n_tp:
        raise RuntimeError(
            f"--stage-tp {n_tp} must divide attention heads "
            f"({cfg.num_attention_heads}), kv heads ({cfg.kv_heads}), "
            f"and intermediate size ({cfg.intermediate_size})")
    if (l - 1) % 4 or r % 4:
        raise RuntimeError(f"--stage-tp requires block-aligned stages; "
                           f"[{l}, {r}] cuts mid-block")
    _, params, shard_cfg = registry.module_shard_factory(
        args.model_name, args.model_file, l, r, stage=stage, dtype=dtype,
        params=restored, unroll=True)
    mesh = Mesh(np.asarray(local[:n_tp]), ("tp",))
    block_fn = tp.make_tp_block_fn(cfg, mesh)
    # shard block-by-block, dropping each unsharded block as it is placed,
    # so peak memory is the stage + one block rather than two full stages
    blocks = list(params["blocks"])
    params["blocks"] = None
    sharded_blocks = []
    for i, bp in enumerate(blocks):
        sharded_blocks.append(tp.shard_block_params(cfg, bp, mesh))
        blocks[i] = None
    sharded_blocks = tuple(sharded_blocks)
    family = entry.family
    embed_fn = jax.jit(lambda p, x: family.embed(p, x, cfg))
    final_fn = jax.jit(lambda p, x: family.finalize(p, x, cfg))
    embed_p = params.get("embeddings")
    final_p = params.get("final")
    logger.info("stage %d: %d block(s) TP-sharded over %d local devices",
                stage, len(sharded_blocks), n_tp)

    def stage_fn(_params, x):
        if shard_cfg.is_first:
            x = embed_fn(embed_p, x)
        for bp in sharded_blocks:
            x = block_fn(bp, x)
        if shard_cfg.is_last:
            x = final_fn(final_p, x)
        return x

    return stage_fn, {}


def _handle_corrupt_results(ctx, src: int, channel: int, exc) -> None:
    """BELT-AND-BRACES handler: with --wire-crc the transport reader
    verifies and recovers corrupt frames before they ever reach a
    consumer, so this only fires on a config mismatch (producer armed
    CRC, this receiver's PIPEEDGE_WIRE_CRC off). Count it, note it, and
    request a latest-frame resend (no seq is known here). In failover
    mode the ledger dedupes and re-orders the replayed frame by
    microbatch id; without a ledger FIFO label pairing may shift by one
    — the same caveat DCN_SEND_RETRIES carries outside failover mode."""
    from pipeedge_tpu.comm import dcn
    dcn.FRAMES_CORRUPT.inc(peer=str(src))
    flight.note("frame_corrupt", peer=src, error=str(exc))
    logger.error("results: corrupt frame from rank %d (%s); requesting "
                 "resend", src, exc)
    try:
        ctx.request_resend(src, channel)
    except OSError as rexc:
        logger.error("resend request to rank %d failed: %s", src, rexc)


def _dcn_round(args, ctx, rnd, stage_layers, stage_quant, stage_ranks,
               ubatches, labels, dtype, results_target,
               ledger: Optional[_MicrobatchLedger] = None,
               stop_base: Optional[int] = None) -> Optional[str]:
    """One schedule round on a live DCN fleet: (data rank) broadcast the
    schedule, build this rank's stage if it is in the schedule, stream the
    batch, stop; (worker) build, run until this round's CMD_STOP.

    With a `ledger` (failover mode at the data rank) every frame carries a
    leading microbatch-id tensor, only unacknowledged microbatches are fed,
    and a mid-round stage death ends the round with status "failover"
    (survivor results drained) instead of raising — the caller re-schedules
    and replays. Returns "ok" on completion, "failover" on a survivable
    death, None on worker ranks."""
    import jax.numpy as jnp

    from pipeedge_tpu.comm import dcn

    rank, data_rank = args.rank, args.data_rank
    failover_mode = args.on_peer_death == "failover"
    # frame integrity (--wire-crc / PIPEEDGE_WIRE_CRC): v2 frames carry a
    # checksum trailer, verified before decode; a corrupt frame requests
    # one bounded resend over the control channel. NaN guard
    # (PIPEEDGE_NAN_GUARD=1): activations checked at stage boundaries.
    wire_crc = getattr(args, "wire_crc", False) or _wire_crc_enabled()
    guard_on = nan_guard.nan_guard_enabled()
    # cross-round frame isolation (see dcn.CHANNEL_ROUND_PARITY)
    parity = dcn.CHANNEL_ROUND_PARITY * (rnd % 2)
    # an ABORTING death is terminal for the whole run — stop_info is never
    # reset, so a death notification landing between rounds cannot be
    # erased (failover-mode deaths live in dead_ranks instead)
    if stop_info[0] is not None:
        raise RuntimeError(f"rank {rank}: pipeline aborted: rank "
                           f"{stop_info[0]} died")
    # fresh round state BEFORE the schedule goes out: once peers have the
    # schedule they may finish the round (CMD_STOP) at any time
    t_round0 = time.monotonic_ns()
    stop_event.clear()
    if rank == data_rank:
        # schedule resolved by the caller; broadcast it (CMD_SCHED,
        # reference runtime.py:441-445), skipping confirmed-dead ranks so
        # a failover schedule reaches every survivor without stalling
        with dead_lock:
            gone = set(dead_ranks)
        ctx.cmd_broadcast(CMD_SCHED, [
            np.asarray(stage_layers, np.int32),
            np.asarray(stage_quant, np.int32),
            np.asarray(stage_ranks, np.int32),
            # the global round index: workers derive channel parity and
            # their stop baseline from it, which is what lets a REJOINED
            # worker (who missed earlier rounds) fall into the sequence
            np.asarray(rnd, np.int32)], exclude=gone)

    try:
        my_stages = [i for i, r in enumerate(stage_ranks) if r == rank]
        stage = None
        if my_stages:
            assert len(my_stages) == 1, \
                "one stage per rank (reference p2p semantics)"
            i = my_stages[0]
            l, r = stage_layers[i]
            restored = None
            if args.stage_ckpt:
                # per-stage Orbax restore: this rank reads exactly its
                # own shard from disk (utils/checkpoint.py); validated
                # against the runtime schedule via the manifest
                from pipeedge_tpu.utils import checkpoint as ckpt_utils
                ckpt_utils.check_stage_compatible(
                    args.stage_ckpt, args.model_name, i, (l, r))
                restored = ckpt_utils.load_stage_checkpoint(
                    args.stage_ckpt, i)
            if args.stage_tp > 1:
                if args.tp_quant_bits:
                    # per-round collective accounting: the tally records
                    # traced sites; this round's fold (in the finally
                    # below) must not re-count a previous round's build
                    from pipeedge_tpu.ops import qcollectives
                    qcollectives.reset_trace_tally()
                fn, params = _make_tp_stage(args, l, r, i, dtype, restored)
            else:
                fn, params, _ = registry.module_shard_factory(
                    args.model_name, args.model_file, l, r, stage=i,
                    dtype=dtype, params=restored)
            out_bit = stage_quant[i] if i < len(stage_layers) - 1 else 0
            is_first, is_last = i == 0, i == len(stage_layers) - 1
            if args.stage_tp <= 1:
                # colocated hand-offs INTO this rank land on its compute
                # device (device-to-device move in dcn._put_on_device; a
                # same-device buffer passes through untouched). TP stages
                # keep the default: their jit places inbound host arrays
                # per its own in_shardings, and a forced single-device
                # commit would fight the mesh.
                import jax
                ctx.set_local_device(jax.local_devices()[0])
            # adaptive policy (env ADAPTIVE_QUANT): this rank adapts its
            # own output edge on its own measured 'send' window, exactly
            # the reference's per-rank hook (runtime.py:121-216). The
            # bitwidth travels on the wire, so the consumer needs no
            # coordination.
            edge = None if is_last else _EdgeQuantState(out_bit)
            adaptive = None if edge is None else _make_adaptive_callback(
                [edge], get_window_size())
            ubatch_idx = [0]
            mb_seq = [0]   # dispatch-order fallback mb id (non-failover
            # frames carry no microbatch id on the wire)

            # head stage is fed over the wire from the data rank
            # (self-connection over loopback when colocated) on the FEED
            # channel; the last stage's results ride the RESULTS channel.
            # Distinct channels keep a colocated schedule's feed, edge,
            # and result streams demultiplexed — and keep feed bytes out
            # of the adaptive policies' edge telemetry.
            rank_src = stage_ranks[i - 1] if not is_first else data_rank
            rank_dst = stage_ranks[i + 1] if not is_last else data_rank

            # per-edge bitwidth handshake (control channel): ask the
            # consuming rank what it accepts BEFORE streaming. The frame
            # header still carries the actual bitwidth; `negotiate`
            # below also re-caps any bitwidth the adaptive policy later
            # selects, so the stream never leaves the agreed capability.
            # On timeout keep the proposal (any consumer in this tree
            # can decode any supported bitwidth from the header alone).
            agreed_bits: dict = {0: 0}

            def negotiate(proposed: int, timeout: float = 5.0) -> int:
                agreed = agreed_bits.get(proposed)
                if agreed is None:
                    try:
                        agreed = ctx.negotiate_edge_bits(rank_dst, proposed,
                                                         timeout=timeout)
                        if agreed != proposed:
                            logger.info("edge rank %d->%d: bitwidth "
                                        "negotiated %d -> %d", rank,
                                        rank_dst, proposed, agreed)
                    except queue.Empty:
                        logger.warning(
                            "edge rank %d->%d: bitwidth handshake timed "
                            "out; keeping bit=%d", rank, rank_dst, proposed)
                        agreed = proposed
                    agreed_bits[proposed] = agreed
                _EDGE_BITS.set(agreed, edge=f"{rank}->{rank_dst}")
                return agreed

            if edge is not None and edge.quant_bit:
                edge.quant_bit = negotiate(edge.quant_bit,
                                           timeout=min(30.0,
                                                       args.sched_timeout))

            # transport-tier handshake for this stage's OUTPUT edge
            # (docs/DCN_WIRE.md selection matrix): colocated consumers
            # take device buffers straight off this process's queues —
            # readback then skips the D2H finalize entirely — remote
            # consumers declare zero-copy vs legacy socket. Timeout or
            # an unreachable peer keeps the (always-correct) socket path.
            edge_tier = [None]
            try:
                edge_tier[0] = ctx.negotiate_edge_path(
                    rank_dst, timeout=min(10.0, args.sched_timeout))
                _EDGE_PATH.set(dcn.PATH_CODES[edge_tier[0]],
                               edge=f"{rank}->{rank_dst}")
            except (queue.Empty, OSError) as exc:
                logger.warning("edge rank %d->%d: transport-path "
                               "handshake failed (%s); keeping the "
                               "socket path", rank, rank_dst, exc)

            # Overlapped work contract (DcnPipelineStage dispatch/readback
            # split): dispatch decodes the inbound frame ON device, runs
            # the shard step, and quantizes the output edge ON device
            # (wire v2) — returning with only async D2H copies of the
            # packed payload in flight. Readback (the send thread) drains
            # those copies while THIS thread dispatches the next
            # microbatch: compute, device->host copy, and socket send
            # overlap instead of serializing.
            def dispatch_cb(tensors):
                mbid = None
                if failover_mode:
                    # failover frames lead with the microbatch id: strip it
                    # host-side here, re-attach in readback — the id never
                    # enters the jitted stage step
                    mbid, tensors = tensors[0], tensors[1:]
                if is_first:
                    payload = jnp.asarray(tensors[0], dtype=dtype
                                          if tensors[0].dtype.kind == 'f'
                                          else None)
                else:
                    try:
                        payload = _wire_decode(tensors, dtype)
                    except WireCorruptError as exc:
                        # belt-and-braces: the transport reader verifies
                        # CRC-flagged frames before enqueueing, so this
                        # only fires on a config mismatch (producer
                        # armed, this receiver's PIPEEDGE_WIRE_CRC off).
                        # Drop + request a latest-frame resend; the
                        # replay re-enters this stage's recv loop.
                        dcn.FRAMES_CORRUPT.inc(peer=str(rank_src))
                        flight.note("frame_corrupt", peer=rank_src,
                                    error=str(exc))
                        logger.error("stage %d: corrupt frame from rank "
                                     "%d (%s); requesting resend", i,
                                     rank_src, exc)
                        try:
                            ctx.request_resend(rank_src,
                                               dcn.CHANNEL_DATA + parity)
                        except OSError as rexc:
                            logger.error("resend request to rank %d "
                                         "failed: %s", rank_src, rexc)
                        return dcn.DcnPipelineStage.SKIP
                # mbid is the host-side wire tensor stripped above,
                # never a device array: the asarray cannot sync
                mb = (int(np.asarray(mbid).reshape(-1)[0])  # pipelint: disable=PL303
                      if mbid is not None else mb_seq[0])
                mb_seq[0] += 1
                if guard_on:
                    # opt-in NaN/Inf guard at the stage INPUT boundary: a
                    # poisoned microbatch dies loudly here (named error +
                    # postmortem bundle) instead of propagating garbage.
                    # The check is a host sync — exactly why it is opt-in.
                    payload = nan_guard.check_finite(  # pipelint: disable=PL303
                        payload, where=f"stage{i}/input", mb=mb)
                # compute span: host dispatch of the jitted shard step
                # (async under jit — device completion lands in the stage
                # readback span, where the wire payload materializes)
                with telemetry.span("compute", f"stage{i}", stage=i, mb=mb):
                    out = fn(params, payload)
                    pending = _wire_encode_device(
                        out, edge.quant_bit if edge is not None else 0,
                        crc=wire_crc)
                first = out[0] if isinstance(out, tuple) else out
                # keep the raw device output alive through the hand-off
                # queue ONLY when the adaptive policy will read it — at
                # depth N it would otherwise pin N extra microbatches of
                # unquantized activations in device memory
                return (pending, out if adaptive is not None else None,
                        int(first.shape[0]), mbid)

            def readback_cb(item):
                pending, out, n_items, mbid = item
                if edge_tier[0] == dcn.PATH_LOCAL:
                    # colocated consumer: hand the DEVICE buffers off
                    # as-is — no D2H readback, no serialize; the frame
                    # metadata rides the local queue (send_tensors'
                    # negotiated local path)
                    wire = list(pending.parts)
                else:
                    wire = pending.finalize()   # completes the async copies
                # beat-to-beat measurement (no iteration_start: dispatch
                # runs on another thread): in steady state the interval
                # between retiring microbatches IS the per-ubatch time.
                # The round build reset the key's beat baseline, so the
                # first beat never swallows the inter-round gap.
                monitoring.iteration(MONITORING_KEY_MODEL, work=n_items,
                                     accuracy=r - l + 1, safe=False)
                if adaptive is not None:
                    adaptive(ubatch_idx[0], out)
                    ubatch_idx[0] += 1
                    # re-cap an adaptive move to what the consumer agreed
                    # to accept (the handshake's promise); answers are
                    # cached, so steady-state windows cost no extra RTT
                    if edge.quant_bit:
                        edge.quant_bit = negotiate(edge.quant_bit)
                if mbid is not None:
                    # NOT ascontiguousarray: it would promote the 0-d id
                    # to 1-d (recv-side arrays are already contiguous)
                    wire = [np.asarray(mbid)] + list(wire)
                return wire

            stage = dcn.DcnPipelineStage(
                ctx, rank_src, rank_dst,
                dispatch_cb=dispatch_cb, readback_cb=readback_cb,
                # failover frames lead with the global microbatch id: tag
                # the stage spans with it so replays trace correctly
                mb_of=((lambda ts: int(np.asarray(ts[0]).reshape(-1)[0]))
                       if failover_mode else None),
                # stage-tagged spans: per-stage busy tracks on the merged
                # trace AND the digest windows the rebalancer consumes
                stage=i,
                depth=args.stage_depth or None,
                recv_channel=(dcn.CHANNEL_FEED if is_first
                              else dcn.CHANNEL_DATA) + parity,
                send_channel=(dcn.CHANNEL_RESULTS if is_last
                              else dcn.CHANNEL_DATA) + parity)
            # fresh beat baseline per round: the beat-to-beat 'shard'
            # measurement must not record the inter-round gap (model
            # build, restore, handshake) as its first iteration
            monitoring.iteration_reset(MONITORING_KEY_MODEL)
            stage.start()
        else:
            logger.info("rank %d not in schedule; idling", rank)

        if rank == data_rank:
            if ledger is None:
                for lb in labels:
                    label_queue.put(lb)
                feed_items = None
            else:
                # only unacknowledged microbatches are (re)fed; labels are
                # delivered by the ledger in microbatch order
                feed_items = ledger.pending()
            first_rank = stage_ranks[0]
            last_rank = stage_ranks[-1]

            # transport tier for the FEED edge (data rank -> head stage):
            # when the head stage is colocated — the common `-r 0,...`
            # layout puts stage 0 on the data rank itself — raw inputs
            # hand off in-process instead of riding a loopback socket
            # round trip per microbatch
            try:
                feed_tier = ctx.negotiate_edge_path(
                    first_rank, timeout=min(10.0, args.sched_timeout))
                _EDGE_PATH.set(dcn.PATH_CODES[feed_tier],
                               edge=f"{rank}->{first_rank}:feed")
            except (queue.Empty, OSError) as exc:
                logger.warning("feed edge rank %d->%d: transport-path "
                               "handshake failed (%s); keeping the "
                               "socket path", rank, first_rank, exc)

            def death_hits_schedule() -> bool:
                # a dead IDLE spare is recorded but must not tear down a
                # healthy round (the rebuild + replay cost is real); only
                # a death among this round's stage ranks fails it over.
                # A SCHEDULED rank sitting in benched_ranks is lost too:
                # restart@K:MS can re-exec the victim fast enough that
                # its JOIN is admitted (dead -> benched) BEFORE this
                # loop's next 0.5s poll observes the death — the fresh
                # incarnation holds no stage state and the in-flight
                # microbatches died with the old one, so waiting on the
                # original schedule would ride out the full sched
                # timeout (the test_chaos_restart_rejoins_and_heals
                # flake). Every call site pairs this check with
                # failover_event, so a benched rank only fails a round
                # during an open death episode — never a healthy run.
                with dead_lock:
                    lost = set(dead_ranks) | set(benched_ranks)
                    return bool(lost & set(stage_ranks))

            def results_loop():
                # wire Mbits/time are measured by the transport recv
                # hooks (_register_dcn_monitor_hooks) on the reader
                # thread; this loop only consumes decoded results
                if ledger is not None:
                    # failover mode: keep acking until the ledger is full
                    # or the round is torn down — including the drain
                    # window after a death, when survivors' in-flight
                    # results are still arriving
                    while not stop_event.is_set() \
                            and not ledger.done.is_set():
                        try:
                            # traced variant: the producing incarnation's
                            # epoch keys the ledger's epoch-aware dedupe
                            # (stale incarnations are fenced at the
                            # reader; this is the ledger's own guard),
                            # and the trace context the feed minted rides
                            # the whole loop back — the retire span
                            # closes the request's fleet-wide timeline
                            tensors, epoch, tctx = ctx.recv_tensors_traced(
                                last_rank, timeout=0.5,
                                channel=dcn.CHANNEL_RESULTS + parity)
                        except queue.Empty:
                            continue
                        except ConnectionError:
                            return
                        mbid = int(np.asarray(tensors[0]).reshape(-1)[0])
                        rid = (tctx.rid if tctx is not None
                               else ledger.trace_of(mbid))
                        try:
                            with telemetry.span("results", "deliver",
                                                mb=mbid, rid=rid):
                                out = _wire_decode(tensors[1:], dtype)
                                if guard_on:
                                    out = nan_guard.check_finite(
                                        out, where="results", mb=mbid,
                                        rid=rid)
                                # the ledger retains the DECODED result,
                                # not the wire views — and a pooled recv
                                # buffer is recycled only when nothing
                                # references it (dcn._RecvBufferPool), so
                                # even a retained view could never be
                                # overwritten
                                if not ledger.ack(mbid, np.asarray(out),
                                                  epoch=epoch,
                                                  src=last_rank):
                                    logger.info("failover: duplicate "
                                                "result for microbatch "
                                                "%d dropped", mbid)
                                else:
                                    # periodic snapshot: keeps the replay
                                    # a mid-round death would trigger
                                    # bounded to the unacked window
                                    ledger.maybe_snapshot()
                        except WireCorruptError as exc:
                            # the resent frame re-enters this loop and
                            # acks by id — exactly-once holds
                            _handle_corrupt_results(
                                ctx, last_rank,
                                dcn.CHANNEL_RESULTS + parity, exc)
                    return
                for mbid in range(len(ubatches)):
                    if stop_event.is_set():
                        return
                    try:
                        tensors, _, tctx = ctx.recv_tensors_traced(
                            last_rank, timeout=args.sched_timeout,
                            channel=dcn.CHANNEL_RESULTS + parity)
                    except (queue.Empty, ConnectionError):
                        # timeout, or the last stage died: the peer-death
                        # handler aborts the run; just stop consuming
                        return
                    try:
                        with telemetry.span("results", "deliver", mb=mbid,
                                            rid=tctx.rid if tctx else None):
                            out = _wire_decode(tensors, dtype)
                            if guard_on:
                                out = nan_guard.check_finite(
                                    out, where="results", mb=mbid)
                            handle_results(np.asarray(out))
                    except WireCorruptError as exc:
                        # the replayed frame is consumed by a later
                        # iteration of this loop (count stays whole)
                        _handle_corrupt_results(
                            ctx, last_rank, dcn.CHANNEL_RESULTS + parity,
                            exc)

            results_thread = threading.Thread(target=results_loop,
                                              daemon=True)
            results_thread.start()
            def feed_loop():
                # feeding runs on its own thread: a send backpressured by a
                # stalled pipeline can block in the kernel indefinitely, and
                # the main thread must stay free to abort (peer death) and
                # broadcast CMD_STOP. On send failure the transport's
                # peer-death handler aborts the run; just stop feeding.
                # request dimension of the batch world: each microbatch
                # is a "request" with a fleet-unique id — the trace
                # context rides every hop's frame, the ledger records the
                # rid<->mbid mapping, and trace_report --request replays
                # the admit(feed)->stages->retire timeline across ranks.
                # Minted only when span recording is on: untraced rounds
                # send byte-identical v2 frames.
                def trace_for(mbid):
                    if not telemetry.enabled():
                        return None
                    tctx = telemetry.TraceContext(
                        f"r{rnd}.mb{mbid}", "batch",
                        parent=f"feed.rank{rank}")
                    if ledger is not None:
                        ledger.record_trace(mbid, tctx.rid)
                    return tctx

                try:
                    if ledger is not None:
                        for mbid, u in feed_items:
                            if stop_event.is_set() or (
                                    failover_event.is_set()
                                    and death_hits_schedule()):
                                return
                            tctx = trace_for(mbid)
                            with telemetry.span("feed", f"mb{mbid}",
                                                mb=mbid,
                                                rid=tctx.rid
                                                if tctx else None):
                                ctx.send_tensors(
                                    first_rank,
                                    [np.asarray(mbid, np.int64),
                                     np.asarray(u)],
                                    channel=dcn.CHANNEL_FEED + parity,
                                    trace=tctx)
                        return
                    for mbid, u in enumerate(ubatches):
                        if stop_event.is_set():
                            return
                        tctx = trace_for(mbid)
                        with telemetry.span("feed", f"mb{mbid}", mb=mbid,
                                            rid=tctx.rid if tctx
                                            else None):
                            ctx.send_tensors(first_rank, [np.asarray(u)],
                                             channel=dcn.CHANNEL_FEED
                                             + parity, trace=tctx)
                except OSError as exc:
                    logger.error("feeding stage rank %d failed (%s)",
                                 first_rank, exc)

            failed_over = False
            try:
                tik = time.monotonic()
                batch_total = sum(len(u) for u in ubatches)
                # results_counter is cumulative across rounds
                results_target[0] += batch_total
                target = results_target[0]
                feed_thread = threading.Thread(target=feed_loop, daemon=True)
                feed_thread.start()
                # poll so a peer-death stop aborts the wait immediately
                # instead of riding out the full --sched-timeout
                deadline = time.monotonic() + args.sched_timeout
                complete = False
                # stop_info guards the window where a death notification
                # lands just before this round cleared stop_event
                while not complete and time.monotonic() < deadline \
                        and not stop_event.is_set() \
                        and stop_info[0] is None:
                    if ledger is not None and failover_event.is_set() \
                            and death_hits_schedule():
                        break
                    if ledger is not None:
                        complete = ledger.done.wait(timeout=0.5)
                    else:
                        complete = results_counter.wait_gte(target,
                                                            timeout=0.5)
                if ledger is not None:
                    if not complete and failover_event.is_set() \
                            and death_hits_schedule():
                        # drain the survivors: in-flight results keep
                        # landing for a moment after the death; wait until
                        # the ack stream goes quiet before tearing down
                        quiet_at = ledger.acked_count
                        drain_deadline = time.monotonic() + 5.0
                        while time.monotonic() < drain_deadline:
                            time.sleep(0.4)
                            now_acked = ledger.acked_count
                            if now_acked == quiet_at:
                                break
                            quiet_at = now_acked
                        failed_over = not ledger.done.is_set()
                    complete = ledger.done.is_set()
                else:
                    # last results can land concurrently with an abort
                    complete = complete or results_counter.wait_gte(
                        target, timeout=0)
                tok = time.monotonic()
            finally:
                # CMD_STOP must go out even on failure, or the workers
                # hang until their own timeouts
                ctx.cmd_broadcast(CMD_STOP)
                stop_event.set()
            results_thread.join(timeout=10)
            feed_thread.join(timeout=10)
            if failed_over:
                monitoring.flush()
                return "failover"
            if not complete:
                if ledger is not None and failover_event.is_set() \
                        and death_hits_schedule():
                    monitoring.flush()
                    return "failover"
                # results_counter is cumulative; report this round's share
                delivered = (ledger.acked_count if ledger is not None else
                             results_counter.value - (target - batch_total))
                if stop_info[0] is not None:
                    raise RuntimeError(
                        f"pipeline aborted: rank {stop_info[0]} died "
                        f"mid-run ({delivered}/{batch_total} "
                        "results delivered)")
                raise RuntimeError(
                    f"pipeline delivered {delivered}/"
                    f"{batch_total} results within {args.sched_timeout}s")
            _report(tik, tok, ubatches)
            return "ok"
        else:
            # wait on the stop COUNT, not the event: this round ends at
            # the first CMD_STOP after its schedule arrived (stop_base =
            # stops counted when the CMD_SCHED landed, paired in
            # handle_cmd) — a stop that lands while this worker is still
            # tearing down the previous round is counted, not lost, and a
            # REJOINED worker who missed earlier rounds' stops needs no
            # absolute history. Poll so a LOCALLY detected death (own
            # send failed; own broadcast skips self, so stop_counter
            # never moves) also aborts promptly.
            target = (stop_base + 1) if stop_base is not None else rnd + 1
            deadline = time.monotonic() + args.sched_timeout
            stopped = False
            while not stopped and stop_info[0] is None \
                    and time.monotonic() < deadline:
                stopped = stop_counter.wait_gte(target, timeout=0.5)
            if stop_info[0] is not None:
                raise RuntimeError(
                    f"rank {rank}: pipeline aborted: rank "
                    f"{stop_info[0]} died mid-run")
            if not stopped:
                raise RuntimeError(
                    f"rank {rank}: no CMD_STOP within "
                    f"{args.sched_timeout}s; aborting")
    finally:
        # the round track frames every other span of this round on the
        # merged timeline (trace_report's window)
        telemetry.record("runtime", f"round{rnd}", t_round0,
                         time.monotonic_ns())
        if stage is not None:
            stage.stop()
            if args.stage_tp > 1 and args.tp_quant_bits:
                # fold this stage's quantized-collective wire footprint,
                # STAGE-TAGGED (the per-stage bits-moved attribution the
                # trace report's collectives section promises): one
                # shared block trace per stage, executed once per block
                # per dispatched microbatch
                from pipeedge_tpu.ops import qcollectives
                summary = qcollectives.record_collectives(
                    executions=mb_seq[0] * max(1, (r - l + 1) // 4),
                    stage=i)
                qcollectives.reset_trace_tally()
                logger.info("rank %d stage %d quantized collectives "
                            "(--tp-quant-bits %d): %s", rank, i,
                            args.tp_quant_bits, summary)


def _report(tik, tok, ubatches):
    batch_size = sum(len(u) for u in ubatches)
    latency = tok - tik
    throughput = batch_size / latency if latency > 0 else 0
    logger.info("Latency: %f seconds", latency)
    logger.info("Throughput: %f items/sec", throughput)
    print(telemetry.startup_line())
    print(f"latency_sec={latency:.6f} throughput_items_sec={throughput:.3f}")


def main():
    parser = argparse.ArgumentParser(
        description="Pipeline-parallel inference runtime (TPU-native)",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    parser.add_argument("rank", type=int, help="must be 0 (single controller)")
    parser.add_argument("worldsize", type=int,
                        help="number of pipeline stages (devices)")
    parser.add_argument("-c", "--comm", type=str, default="host",
                        choices=["host", "spmd", "dcn", "p2p", "rpc"],
                        help="pipeline driver; dcn = multi-process TCP "
                             "transport (one rank per process, reference "
                             "p2p semantics); p2p/rpc are host aliases")
    parser.add_argument("-m", "--model-name", type=str,
                        default="google/vit-base-patch16-224",
                        choices=registry.get_model_names())
    parser.add_argument("-M", "--model-file", type=str,
                        help="model weights file (.npz)")
    parser.add_argument("--stage-ckpt", type=str, default=None, metavar="DIR",
                        help="per-stage Orbax checkpoint root (from "
                             "tools/convert_checkpoint.py); each dcn rank "
                             "restores only its own stage shard")
    parser.add_argument("-b", "--batch-size", default=64, type=int)
    parser.add_argument("-u", "--ubatch-size", default=8, type=int)
    parser.add_argument("-t", "--dtype", default="float32",
                        choices=["float32", "bfloat16"])
    # scheduling (reference runtime.py:657-687)
    parser.add_argument("-pt", "--partition", type=str,
                        help="comma-delimited layer pairs, e.g. '1,24,25,48';"
                             " ';'-separated values define live re-schedule "
                             "rounds (dcn only)")
    parser.add_argument("-q", "--quant", type=str,
                        help="comma-delimited per-stage output quant bitwidths"
                             " (';'-separated per re-schedule round)")
    parser.add_argument("-r", "--rank-order", type=str, default=None,
                        help="comma-delimited stage-to-device mapping")
    parser.add_argument("-D", "--data-rank", type=int, default=0,
                        help="rank that drives data/results (dcn mode); "
                             "single-controller drivers always use the host")
    parser.add_argument("--dcn-addrs", type=str, default=None,
                        help="comma-delimited host:port listener address per "
                             "rank (dcn mode); default 127.0.0.1:PORT+rank")
    parser.add_argument("-P", "--port", type=int, default=29600,
                        help="base listener port for dcn mode defaults")
    parser.add_argument("--spmd-dp", type=int, default=1,
                        help="data-parallel mesh axis for the spmd driver "
                             "(devices needed = stages x dp x (tp or sp))")
    parser.add_argument("--spmd-tp", type=int, default=1,
                        help="Megatron tensor-parallel mesh axis for the "
                             "spmd driver: blocks stage-sharded AND "
                             "tp-sharded in one XLA program")
    parser.add_argument("--spmd-sp", type=int, default=1,
                        help="sequence-parallel mesh axis for the spmd "
                             "driver: activations sequence-sharded, exact "
                             "ring attention per block (long-context "
                             "pipelines); exclusive with --spmd-tp")
    parser.add_argument("--spmd-sp-kind", default="ring",
                        choices=["ring", "ulysses"],
                        help="sp attention core: K/V ring rotation or "
                             "Ulysses all-to-all head resharding")
    parser.add_argument("--stage-tp", type=int, default=1,
                        help="shard each dcn stage's blocks Megatron-style "
                             "over N local devices (block-aligned stages): "
                             "pipeline across hosts over DCN, tensor "
                             "parallelism within each host")
    parser.add_argument("--tp-quant-bits", type=int, default=0,
                        choices=[0, 8, 4],
                        help="bitwidth of intra-stage TP/SP collectives "
                             "(EQuARX-style quantized allreduce/all-gather "
                             "over ICI, ops/qcollectives.py): 0 = exact "
                             "full-width psum/all_gather; 8/4 = block-"
                             "scaled int8/int4 ring collectives with an "
                             "f32 accumulator. Gates every tensor.py psum "
                             "site (--spmd-tp, --stage-tp) and the "
                             "sequence-parallel gather (--spmd-sp); see "
                             "docs/QUANT_COLLECTIVES.md")
    parser.add_argument("--stage-depth", type=int, default=0,
                        help="dcn stage pipelining depth: microbatches "
                             "buffered per hand-off queue, letting the next "
                             "microbatch's compute overlap the previous "
                             "one's device->host readback and socket send "
                             "(0 = env DCN_STAGE_DEPTH or 2; 1 restores the "
                             "serialized pre-overlap behavior)")
    parser.add_argument("--sched-timeout", type=float, default=300,
                        help="seconds a worker waits for the schedule / "
                             "results / stop (dcn mode)")
    parser.add_argument("--rebalance", default="off",
                        choices=["off", "auto"],
                        help="closed-loop rebalancing from live telemetry "
                             "(docs/REBALANCE.md). dcn mode: the data rank "
                             "re-solves the layer partition each round from "
                             "measured per-stage timings (span digests over "
                             "the command channel) and applies it at the "
                             "next round boundary via CMD_SCHED — pass the "
                             "flag to every rank. host mode with "
                             "--measure-rounds > 1: adapt the microbatch "
                             "size to the measured steady-state stage time "
                             "vs fill/drain overhead")
    parser.add_argument("--rebalance-threshold", type=float, default=0.10,
                        help="minimum predicted relative bottleneck gain "
                             "before a re-partition is applied (hysteresis: "
                             "a balanced fleet never churns)")
    parser.add_argument("--rebalance-cooldown", type=int, default=1,
                        help="full rounds to wait after a rebalance before "
                             "considering another (no oscillation while "
                             "the previous re-plan is still being measured)")
    parser.add_argument("--rebalance-confirm", type=int, default=1,
                        help="extra consecutive windows that must blame the "
                             "SAME bottleneck stage before a re-partition "
                             "is applied (filters round-to-round drift; a "
                             "real straggler persists; 0 = act on the "
                             "first actionable window)")
    parser.add_argument("--rounds", type=int, default=1,
                        help="dcn mode: run the schedule this many rounds "
                             "(same batch each round) — the boundaries "
                             "--rebalance auto re-plans at; equivalent to "
                             "repeating the schedule with ';'")
    parser.add_argument("--on-peer-death", default="abort",
                        choices=["abort", "failover"],
                        help="dcn mode reaction to a stage rank dying "
                             "mid-run: abort the fleet (default, the "
                             "pre-failover semantics) or re-schedule over "
                             "the survivors and replay unacknowledged "
                             "microbatches (must be uniform across the "
                             "fleet; results are exactly-once by "
                             "microbatch id)")
    parser.add_argument("--on-peer-rejoin", default="spare",
                        choices=["ignore", "spare", "heal"],
                        help="dcn mode reaction to a confirmed-dead rank "
                             "passing the JOIN admission handshake (a "
                             "restarted incarnation with a higher "
                             "DCN_EPOCH): ignore refuses re-admission "
                             "(deaths stay terminal), spare re-admits it "
                             "as live idle capacity for FUTURE failovers, "
                             "heal additionally restores the pre-failure "
                             "partition (or re-expands onto the restored "
                             "rank) at the next round boundary — "
                             "docs/FAULT_TOLERANCE.md")
    parser.add_argument("--on-peer-degraded", default="ignore",
                        choices=["ignore", "quarantine"],
                        help="dcn mode reaction to a GRAY-failing peer — "
                             "alive and beating, but its EWMA health "
                             "score (relative stage service time, "
                             "heartbeat RTT, send retries) confirmed a "
                             "straggler: ignore scores and reports only; "
                             "quarantine benches the rank at the next "
                             "round boundary (a planned drain — its "
                             "stage moves to a spare via the failover "
                             "re-plan, no replay) and readmits it "
                             "through probation when the score recovers. "
                             "Forces span recording on (the scorer reads "
                             "the rebalancer's digest windows); pass the "
                             "flag to every rank — "
                             "docs/FAULT_TOLERANCE.md gray failures")
    parser.add_argument("--degraded-threshold", type=float, default=0.4,
                        help="EWMA degradation score at which a rank "
                             "turns suspect (readmit threshold is half "
                             "this: the hysteresis band)")
    parser.add_argument("--degraded-confirm", type=int, default=2,
                        help="consecutive bad windows AFTER the suspect "
                             "entry before quarantine (false-positive "
                             "protection; the entry window never "
                             "convicts alone)")
    parser.add_argument("--degraded-readmit", type=int, default=2,
                        help="consecutive recovered windows before a "
                             "quarantined rank readmits on probation")
    parser.add_argument("--autoscale-ranks", default="off",
                        choices=["off", "advise", "auto"],
                        help="dcn mode closed-loop capacity control over "
                             "the pipeline partition (the rank-level "
                             "half of serving/autoscale.py): scale-up "
                             "expands onto idle survivors via the "
                             "plan_rejoin cascade at a round boundary, "
                             "scale-down benches the least-needed rank "
                             "through the failover re-plan (dry-run "
                             "verified — an un-runnable contraction "
                             "renders as `held`). advise logs decisions "
                             "without acting; auto acts. Data rank "
                             "drives; forces span recording on "
                             "(signals come from the rebalancer's "
                             "digest windows)")
    parser.add_argument("--autoscale-min", type=int, default=2,
                        help="stage-count floor the capacity controller "
                             "never contracts below")
    parser.add_argument("--autoscale-max", type=int, default=0,
                        help="stage-count ceiling (0 = world size)")
    parser.add_argument("--autoscale-confirm", type=int, default=2,
                        help="consecutive same-direction measured "
                             "windows before a capacity decision")
    parser.add_argument("--autoscale-cooldown", type=float, default=0.0,
                        help="seconds between capacity decisions "
                             "(reversals double it — the flap damper)")
    parser.add_argument("--autoscale-rank-high", type=float, default=0.75,
                        help="bottleneck stage service seconds per "
                             "microbatch that count as up pressure")
    parser.add_argument("--autoscale-rank-low", type=float, default=0.05,
                        help="bottleneck service seconds below which "
                             "the pipeline counts as over-provisioned")
    parser.add_argument("--wire-crc", action="store_true",
                        help="frame integrity: checksum every wire-v2 "
                             "frame (CRC32C when the wheel is present, "
                             "zlib CRC32 otherwise; algorithm rides the "
                             "frame), verify on receive, and recover a "
                             "corrupt frame with one bounded resend "
                             "over the control channel (cap = max(1, "
                             "DCN_SEND_RETRIES)). Equivalent to env "
                             "PIPEEDGE_WIRE_CRC=1; pass to every rank")
    parser.add_argument("--heartbeat-interval", type=float, default=0.0,
                        help="dcn liveness plane: seconds between heartbeat "
                             "frames to every peer (0 = env "
                             "DCN_HEARTBEAT_INTERVAL or disabled); catches "
                             "HUNG ranks whose sockets stay open")
    parser.add_argument("--heartbeat-miss", type=int, default=0,
                        help="missed-beat threshold before a silent peer "
                             "is declared dead (0 = env DCN_HEARTBEAT_MISS "
                             "or 3)")
    parser.add_argument("--save-results", type=str, default=None,
                        metavar="NPZ",
                        help="save every delivered result microbatch (in "
                             "delivery order) to this .npz — lets chaos "
                             "runs be compared bit-for-bit against "
                             "no-fault runs")
    parser.add_argument("--platform", type=str, default="auto",
                        choices=["auto", "cpu"],
                        help="force the JAX CPU backend (testing multi-"
                             "process dcn pipelines without TPU chips)")
    parser.add_argument("--trace", type=str, default=None, metavar="DIR",
                        help="capture a JAX profiler trace of the run into "
                             "DIR (view with tensorboard/perfetto)")
    parser.add_argument("--trace-spans", type=str, default=None,
                        metavar="OUT",
                        help="record runtime spans (dispatch/compute/"
                             "readback/wire/feed/results/failover) and "
                             "write a merged Perfetto-loadable trace JSON "
                             "to OUT. In dcn mode the data rank gathers "
                             "every rank's spans over the command channel "
                             "with NTP-style clock alignment (pass the "
                             "flag to every rank); analyze with "
                             "tools/trace_report.py")
    parser.add_argument("--measure-rounds", type=int, default=1,
                        help="host driver: run the ubatch stream this many "
                             "times, printing a latency line per round "
                             "(round 0 includes the XLA compiles; later "
                             "rounds measure the warm pipeline)")
    parser.add_argument("-sm", "--sched-models-file", default=None, type=str)
    parser.add_argument("-sdt", "--sched-dev-types-file", default=None, type=str)
    parser.add_argument("-sd", "--sched-dev-file", default=None, type=str)
    parser.add_argument("-H", "--hosts", type=str,
                        help="comma-delimited hosts/chips for schedule mapping")
    # dataset (reference runtime.py:688-705)
    parser.add_argument("--dataset-name", type=str, default="synthetic",
                        choices=["synthetic", "ImageNet", "CoLA"])
    parser.add_argument("--dataset-root", type=str)
    parser.add_argument("--dataset-split", default='val', type=str)
    parser.add_argument("--dataset-indices-tsv", type=str,
                        help="TSV file with dataset indices to use")
    parser.add_argument("--dataset-shuffle", action="store_true")
    args = parser.parse_args()

    if args.platform == "cpu":
        from pipeedge_tpu.utils import force_host_cpu_devices
        force_host_cpu_devices(max(1, args.worldsize))

    if args.stage_ckpt and args.comm != "dcn":
        parser.error("--stage-ckpt is a dcn-mode option (per-rank restore); "
                     "single-controller drivers load via -M/--model-file")

    if args.rank != 0 and args.comm != "dcn":
        logger.warning("Single-controller runtime: only rank 0 runs; "
                       "rank %d exits immediately (all devices are driven "
                       "from rank 0). Use --comm dcn for one-process-per-"
                       "rank operation.", args.rank)
        return

    hosts = args.hosts.split(',') if args.hosts else None
    indices = None
    if args.dataset_indices_tsv:
        with open(args.dataset_indices_tsv) as f:
            indices = [int(line.split('\t')[0]) for line in f if line.strip()]

    # ';'-separated -pt/-q/-r values define multiple schedule ROUNDS: the
    # dcn fleet re-schedules live at each run boundary (CMD_SCHED). A single
    # value applies to every round.
    pt_rounds = args.partition.split(';') if args.partition else [None]
    q_rounds = args.quant.split(';') if args.quant else [None]
    r_rounds = args.rank_order.split(';') if args.rank_order else [None]
    n_rounds = max(len(pt_rounds), len(q_rounds), len(r_rounds))
    if n_rounds > 1 and args.comm != "dcn":
        parser.error("';'-separated re-schedule rounds require --comm dcn")
    if args.rounds < 1:
        parser.error("--rounds must be >= 1")
    if args.rounds > 1:
        if args.comm != "dcn":
            parser.error("--rounds requires --comm dcn (use "
                         "--measure-rounds for the host driver)")
        if n_rounds > 1:
            parser.error("--rounds cannot combine with ';'-separated "
                         "re-schedule rounds (pick one)")
    if args.rebalance == "auto":
        if args.comm == "spmd":
            parser.error("--rebalance auto applies to the dcn driver "
                         "(partition re-solve) and the host driver "
                         "(adaptive microbatching), not spmd")
        if len(set(pt_rounds)) > 1:
            # the rebalancer assumes rounds repeat the same workload; it
            # would silently overwrite deliberately distinct partitions
            parser.error("--rebalance auto cannot combine with distinct "
                         "';'-separated partitions")
        if args.stage_ckpt:
            # the per-stage checkpoint manifest pins the partition; a
            # re-cut would fail every rank's compatibility check on the
            # next round's restore
            parser.error("--rebalance auto cannot combine with "
                         "--stage-ckpt (the checkpoint manifest pins the "
                         "partition)")
        # a single round leaves no boundary to re-plan at: refuse the
        # silent no-op (matches the validation style of the combinations
        # above)
        if args.comm == "dcn" and args.rounds == 1 and n_rounds == 1:
            parser.error("--rebalance auto needs round boundaries to "
                         "re-plan at: pass --rounds N (or ';'-separated "
                         "schedule rounds)")
        if args.comm != "dcn" and args.measure_rounds <= 1:
            parser.error("--rebalance auto on the host driver adapts the "
                         "microbatch size BETWEEN measure rounds: pass "
                         "--measure-rounds N > 1")
    if args.on_peer_degraded == "quarantine":
        if args.comm != "dcn":
            parser.error("--on-peer-degraded quarantine applies to the "
                         "dcn driver (per-process ranks)")
        # quarantine acts at round boundaries, like --rebalance auto:
        # refuse the silent no-op of a single-round run
        if args.rounds == 1 and n_rounds == 1:
            parser.error("--on-peer-degraded quarantine acts at round "
                         "boundaries: pass --rounds N (or ';'-separated "
                         "schedule rounds)")
    if args.autoscale_ranks != "off":
        if args.comm != "dcn":
            parser.error("--autoscale-ranks applies to the dcn driver "
                         "(per-process ranks)")
        if args.rounds == 1 and n_rounds == 1:
            parser.error("--autoscale-ranks acts at round boundaries: "
                         "pass --rounds N (or ';'-separated schedule "
                         "rounds)")
        if args.autoscale_ranks == "auto" \
                and args.on_peer_death != "failover":
            parser.error("--autoscale-ranks auto needs --on-peer-death "
                         "failover: a planned bench rides the failover "
                         "re-plan cascade (advise mode only observes)")
        if args.autoscale_min < 1:
            parser.error("--autoscale-min must be >= 1")
        if args.autoscale_confirm < 1:
            parser.error("--autoscale-confirm must be >= 1")
    if args.wire_crc:
        # one process-wide switch (env), so the transport's resend cache
        # and chaos corrupt@K see the same setting the codec does
        from pipeedge_tpu.comm.wire import ENV_WIRE_CRC
        os.environ[ENV_WIRE_CRC] = "1"
    if args.tp_quant_bits:
        has_tp_sites = (args.stage_tp > 1
                        or (args.comm == "spmd"
                            and (args.spmd_tp > 1 or args.spmd_sp > 1)))
        if not has_tp_sites:
            parser.error("--tp-quant-bits gates intra-stage TP/SP "
                         "collectives, but no TP axis is active: pass "
                         "--spmd-tp/--spmd-sp > 1 (--comm spmd) or "
                         "--stage-tp > 1 (--comm dcn)")
        # one global trace-time flag (layers.set_fast_numerics idiom):
        # set BEFORE any driver traces a TP block body, and inherited by
        # dcn worker processes through their own arg parse
        from pipeedge_tpu.parallel import tensor as _tensor_flags
        _tensor_flags.set_tp_quant_bits(args.tp_quant_bits)
    if args.stage_tp > 1 and args.comm != "dcn":
        parser.error("--stage-tp requires --comm dcn (per-rank local TP; "
                     "use the spmd driver's mesh axes for single-controller "
                     "tp)")
    if args.stage_tp > 1:
        # fail at parse time, not mid-round after the schedule broadcast
        # (a late failure on one rank strands the rest of the fleet until
        # the peer-death abort)
        cfg = registry.get_model_config(args.model_name)
        if cfg.num_attention_heads % args.stage_tp \
                or cfg.intermediate_size % args.stage_tp \
                or cfg.kv_heads % args.stage_tp:
            parser.error(
                f"--stage-tp {args.stage_tp} must divide attention heads "
                f"({cfg.num_attention_heads}), kv heads ({cfg.kv_heads}), "
                f"and intermediate size ({cfg.intermediate_size}) of "
                f"{args.model_name}")
        for spec in pt_rounds:
            if not spec:
                continue
            nums = [int(x) for x in spec.split(',')]
            for l, r in zip(nums[::2], nums[1::2]):
                if (l - 1) % 4 or r % 4:
                    parser.error(f"--stage-tp requires block-aligned "
                                 f"stages; [{l}, {r}] cuts mid-block")
    for opt, specs in (("-pt", pt_rounds), ("-q", q_rounds),
                       ("-r", r_rounds)):
        if 1 < len(specs) != n_rounds:
            parser.error(f"{opt}: {len(specs)} ';'-rounds given but "
                         f"{n_rounds} rounds defined; give 1 or {n_rounds}")

    def _round_spec(specs, i):
        return specs[i] if len(specs) > 1 else specs[0]

    is_dcn_worker = args.comm == "dcn" and args.rank != args.data_rank
    if is_dcn_worker:
        # schedule arrives via CMD_SCHED; only the data rank loads data
        schedules = []
        stage_layers, stage_quant, stage_ranks = [], [], []
        ubatches, labels = [], []
    else:
        schedules = []
        for i in range(n_rounds):
            partition = None
            pt_spec = _round_spec(pt_rounds, i)
            if pt_spec:
                nums = [int(x) for x in pt_spec.split(',')]
                assert len(nums) % 2 == 0
                partition = list(zip(nums[::2], nums[1::2]))
            q_spec = _round_spec(q_rounds, i)
            quant = [int(x) for x in q_spec.split(',')] if q_spec else None
            r_spec = _round_spec(r_rounds, i)
            rank_order = [int(x) for x in r_spec.split(',')] \
                if r_spec else None
            schedules.append(get_pipeline_sched(
                args.worldsize, hosts, partition, quant, rank_order,
                args.model_name, args.ubatch_size, args.sched_models_file,
                args.sched_dev_types_file, args.sched_dev_file,
                dtype=args.dtype))
        # --rounds N: the single resolved schedule runs N times (the round
        # boundaries --rebalance auto re-plans at)
        schedules = schedules * max(1, args.rounds)
        stage_layers, stage_quant, stage_ranks = schedules[0]

        dataset = load_dataset(
            {'name': args.dataset_name, 'root': args.dataset_root,
             'split': args.dataset_split, 'indices': indices,
             'shuffle': args.dataset_shuffle},
            args.model_name, args.batch_size, args.ubatch_size)
        ubatches, labels = [], []
        for inputs, lbls in data_utils.batch_dataset(dataset, args.ubatch_size):
            ubatches.append(inputs)
            labels.append(lbls)

    window_size = get_window_size()
    monitoring.init(MONITORING_KEY_MODEL, window_size, work_type='items',
                    acc_type='layers')
    monitoring.add_key(MONITORING_KEY_OUTPUT, work_type='classifications',
                       acc_type='correct')
    monitoring.add_key(MONITORING_KEY_SEND, work_type='Mbits')
    monitoring.add_key(MONITORING_KEY_RECV, work_type='Mbits')
    monitoring.add_key(MONITORING_KEY_QUANT_ENCODE, acc_type='bits')
    monitoring.add_key(MONITORING_KEY_QUANT_DECODE, acc_type='bits')
    monitoring.add_key(MONITORING_KEY_LIVENESS, work_type='beats',
                       acc_type='rank')
    monitoring.add_key(MONITORING_KEY_HB_RTT, work_type='ms',
                       acc_type='rank')

    global _results_sink
    if args.save_results and not is_dcn_worker:
        _results_sink = []

    if args.trace_spans or (args.comm == "dcn"
                            and (args.rebalance == "auto"
                                 or args.on_peer_degraded == "quarantine"
                                 or args.autoscale_ranks != "off")):
        # every rank records; in dcn mode the data rank merges the fleet
        # (workers serve their rings over _MSG_SPANS), single-controller
        # drivers write their own single-rank timeline below. The
        # rebalancer's digests come from the same recorder (workers answer
        # _MSG_SPANS digest requests inline), so --rebalance auto records
        # even without a trace destination — and the peer-health scorer
        # (--on-peer-degraded quarantine) reads the same digest windows.
        telemetry.configure(rank=args.rank if args.comm == "dcn" else 0)

    report_devices()
    try:
        comm = args.comm
        if comm in ("p2p", "rpc"):
            comm = "host"
        if comm == "spmd":
            try:
                spmd.partition_to_blocks(stage_layers)
            except ValueError as exc:
                logger.warning("%s; falling back to host driver", exc)
                comm = "host"
        from pipeedge_tpu.utils import tracing
        trace_dir = args.trace
        if trace_dir and comm == "dcn":
            # per-rank session dirs: same-host ranks would otherwise clobber
            # each other's hostname-keyed profile files
            trace_dir = os.path.join(trace_dir, f"rank{args.rank}")
        with tracing.trace(trace_dir):
            if comm == "dcn":
                # waits for its own results/stop internally (multi-process)
                run_pipeline_dcn(args, schedules, ubatches, labels)
            elif comm == "spmd":
                run_pipeline_spmd(args, stage_layers, stage_quant,
                                  stage_ranks, ubatches, labels)
            else:
                run_pipeline_host(args, stage_layers, stage_quant, stage_ranks,
                                  ubatches, labels)
        if comm != "dcn":
            assert results_counter.wait_gte(
                sum(len(u) for u in ubatches), timeout=300)
            if args.trace_spans and telemetry.recorder() is not None:
                # single-controller drivers: one rank, no collection pass
                from pipeedge_tpu.telemetry import chrome_trace
                spans = telemetry.recorder().snapshot()
                chrome_trace.dump_trace(spans, args.trace_spans)
                logger.info("trace-spans: %d span(s) -> %s", len(spans),
                            args.trace_spans)
        if _results_sink is not None:
            np.savez(args.save_results,
                     *[np.asarray(o) for o in _results_sink])
            logger.info("saved %d result microbatch(es) to %s",
                        len(_results_sink), args.save_results)
    finally:
        monitoring.finish()


if __name__ == "__main__":
    logging.basicConfig(
        level=logging.INFO,
        handlers=[logging.StreamHandler(sys.stdout),
                  logging.FileHandler("runtime.log", mode='a')])
    from pipeedge_tpu.utils import enable_compile_cache
    enable_compile_cache()
    main()
