"""Streaming: operator DAGs executed as actor pipelines.

Parity: `streaming/python/streaming.py` (`ExecutionGraph`, operators,
actor channels over the C++ data plane N27) — the API surface
(StreamingContext -> source -> map/flat_map/filter/key_by/window/
reduce/sink) compiles to a chain of operator actors connected by
ordered actor calls (the framework's actor streams ARE the channel
layer: per-caller sequence numbers give the same ordered-delivery
guarantee the reference's ring-buffer channels provide). key_by
hash-partitions items across the downstream operator's parallel
instances.

Flow control (parity: the bounded ring buffers of
`streaming/src/ring_buffer.cc` + `data_writer.cc` backpressure): every
edge carries at most `credits` UNACKED items. At the credit limit the
sender blocks on the OLDEST outstanding push (ordered actor streams
complete in order) before pushing more, so a fast source stalls
against a slow sink instead of growing an unbounded queue —
back-pressure propagates hop by hop up to the driver's source loop.

Failure recovery (parity: `streaming/src/data_writer.cc` channel
recreation on reader/writer restart; the checkpoint-coverage idea is
the classic upstream-backup protocol): operator actors run with
`max_restarts`; every edge's items carry per-edge SEQUENCE NUMBERS,
and each sender retains items until the downstream's CHECKPOINT covers
them (the downstream reports its checkpoint-covered seq in every ack).
When a drain observes the downstream died, the sender replays every
retained item — retired-but-uncovered first, then the unacked window —
in order, against the restarted actor. The receiver dedups by seq
against its restored state, and REFUSES items past a sequence hole
(crash after ack, before checkpoint: the sender never observed the
death, so its next ordinary push would otherwise silently skip the
lost suffix) by acking `{"replay_from": <applied>}`; the sender then
replays its retention from that point. Net guarantee WITH a `checkpoint_dir`:
**effectively-once** per edge into operator state for deterministic
operators (replays reconstruct exactly the uncheckpointed suffix; no
loss, no double-apply). Without a checkpoint_dir, state restarts EMPTY
and replay covers retained items only — at-least-once delivery of the
recent window, the reference data plane's contract. Nondeterministic
operator fns weaken replay reconstruction to at-least-once. A
downstream that exhausts its restart budget fails the pipeline with
the underlying `ActorDiedError`. Sender retention is bounded by
`checkpoint_interval` + `credits` items per edge.
"""

from __future__ import annotations

import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional

import ray_tpu
from ray_tpu._private import config as _config
from ray_tpu.exceptions import ActorDiedError, ActorUnavailableError


def _default_credits() -> int:
    # Read at use time, not import time, so env overrides applied after
    # import (and `stat --config`'s report) stay truthful.
    return _config.get("RAY_TPU_STREAMING_CREDITS")


def _stable_hash(key) -> int:
    import hashlib
    return int.from_bytes(
        hashlib.md5(repr(key).encode()).digest()[:8], "little")


class EdgeSender:
    """Sender half of one channel edge (module doc: flow control +
    upstream-backup recovery).

    - `inflight`: pushed, unacked (ref, item, key, seq) — the credit
      window.
    - `retired`: acked but not yet covered by the downstream's
      checkpoint — kept for replay after a downstream restart, trimmed
      as acks report growing coverage.
    - `seq`: per-edge monotone counter; the receiver dedups on it.
    """

    def __init__(self, handle, edge_id: str, credits: int,
                 start_seq: int = 0):
        self.handle = handle
        self.edge_id = edge_id
        self.credits = max(1, credits)
        self.seq = start_seq
        self.inflight: deque = deque()  # (ref, item, key, seq)
        self.retired: deque = deque()   # (item, key, seq)
        self.covered = 0

    def push(self, item, key=None) -> None:
        while len(self.inflight) >= self.credits:
            self.drain_oldest()
        self.seq += 1
        self.inflight.append(
            (self.handle.process.remote(item, key, self.seq,
                                        self.edge_id),
             item, key, self.seq))

    def _trim_retired(self) -> None:
        while self.retired and self.retired[0][2] <= self.covered:
            self.retired.popleft()

    def drain_oldest(self, redeliver_timeout_s: float = 30.0) -> None:
        """Complete the oldest unacked push; on downstream death,
        replay everything retained (module doc), retrying until the
        actor comes back or the redelivery budget is exhausted. The
        get itself is UNBOUNDED — a slow-but-alive downstream is
        backpressure, not failure; only an observed death starts the
        redelivery clock."""
        deadline = None
        while True:
            ref, item, key, seq = self.inflight[0]
            try:
                ack = ray_tpu.get(ref)
                if isinstance(ack, dict) and "replay_from" in ack:
                    # The receiver refused this item: it restarted with
                    # a hole between its restored state and our stream
                    # (crash after ack, before checkpoint). Replay the
                    # retention — retired-but-uncovered first, then the
                    # unacked window (this item included) — and keep
                    # draining the re-pushed stream.
                    # The receiver's own count is the authority, also
                    # DOWNWARDS: without checkpoints a restart forgets
                    # what the old incarnation acked, and a `covered`
                    # kept above it hides the hole from `_replay`, which
                    # then never marks the resync and is refused again,
                    # for ever and at full speed.
                    self.covered = int(ack["replay_from"])
                    self._trim_retired()
                    self._replay()
                    continue
                self.inflight.popleft()
                self.retired.append((item, key, seq))
                if isinstance(ack, int):
                    self.covered = max(self.covered, ack)
                self._trim_retired()
                return
            except (ActorDiedError, ActorUnavailableError):
                now = time.monotonic()
                if deadline is None:
                    deadline = now + redeliver_timeout_s
                elif now > deadline:
                    raise
                time.sleep(0.2)
                self._replay()
            # Task-level errors (user fn raised) are not delivery
            # failures; they propagate out of the get above.

    def _replay(self) -> None:
        """Re-push everything the downstream's checkpoint does not
        cover, in seq order (the receiver dedups anything it has
        already applied post-restore). When retention cannot reach back
        to `covered + 1` (checkpointing off: nothing is retained past
        the ack), the first replayed item carries `resync=True` so the
        receiver accepts the unfillable hole instead of refusing the
        stream forever."""
        items = [(item, key, seq) for item, key, seq in self.retired
                 if seq > self.covered]
        items += [(item, key, seq) for _, item, key, seq
                  in self.inflight]
        self.retired = deque(
            (i, k, s) for i, k, s in self.retired if s <= self.covered)
        resync_first = bool(items) and items[0][2] > self.covered + 1

        def push(i, item, key, seq):
            if resync_first and i == 0:
                return self.handle.process.remote(item, key, seq,
                                                  self.edge_id, True)
            # 4-arg form keeps duck-typed receivers without a resync
            # parameter working (only _OperatorActor-style int acks
            # can ever produce a resync-worthy hole).
            return self.handle.process.remote(item, key, seq,
                                              self.edge_id)

        self.inflight = deque(
            (push(i, item, key, seq), item, key, seq)
            for i, (item, key, seq) in enumerate(items))

    def drain_all(self) -> None:
        while self.inflight:
            self.drain_oldest()


class _OperatorActor:
    """One parallel instance of one operator stage.

    With a `checkpoint_dir`, operator STATE (reduce accumulators,
    window buffers, sink values, per-edge applied seqs, downstream
    emit seqs) survives actor restarts through the framework's
    `Checkpointable` protocol (`actor.py:186`); combined with the
    senders' checkpoint-coverage retention this yields the
    effectively-once contract in the module doc. Without a
    checkpoint_dir the protocol is dormant (`should_checkpoint`
    False), acks report applied seqs directly (senders retain nothing
    beyond the credit window), and state restarts empty.
    """

    def __init__(self, kind: str, fn_bytes, downstream_handles,
                 instance_id: int, credits: int = None,
                 checkpoint_dir: str = None,
                 checkpoint_interval: int = 100,
                 window_size: int = 0):
        import cloudpickle
        self.kind = kind
        self.fn = cloudpickle.loads(fn_bytes) if fn_bytes else None
        self.downstream = downstream_handles
        self.instance_id = instance_id
        self.credits = max(1, credits if credits is not None
                           else _default_credits())
        self._senders = [
            EdgeSender(h, f"{kind}{instance_id}->d{i}", self.credits)
            for i, h in enumerate(downstream_handles)]
        self._state: Dict[Any, Any] = {}  # key -> accumulated value
        self._windows: Dict[Any, list] = {}  # key -> buffered items
        self._window_size = int(window_size)
        self._sink: List[Any] = []
        self._rr = 0
        # Per-upstream-edge seq bookkeeping (module doc).
        self._edge_seq: Dict[str, int] = {}       # last APPLIED
        self._ckpt_edge_seq: Dict[str, int] = {}  # covered by last ckpt
        self._ckpt_dir = checkpoint_dir
        self._ckpt_interval = max(1, int(checkpoint_interval))
        self._since_ckpt = 0

    # -- data plane ------------------------------------------------------
    def process(self, item, key=None, seq=None, edge=None,
                resync=False):
        """Apply one item; returns this edge's checkpoint-covered seq
        (the sender's retention watermark). Duplicate seqs (replays of
        already-applied items) are skipped but still acked.

        GAP HANDLING (effectively-once fix): a seq beyond
        `last_applied + 1` means items were lost in a hole — the
        classic sequence is this operator crashing after acking items
        it had applied but not yet checkpointed, restarting from the
        checkpoint, then receiving the sender's NEXT item. Applying
        past the hole would silently drop the uncheckpointed suffix,
        so the item is REFUSED and `{"replay_from": <applied>}` is
        returned; the sender replays its retention from there (see
        `EdgeSender.drain_oldest`). `resync=True` marks the first item
        of a replay whose sender retains nothing older (checkpointing
        off — at-least-once of the retained window is the documented
        contract): the receiver accepts the hole knowingly and
        fast-forwards its applied seq."""
        if edge is not None and seq is not None:
            applied = self._edge_seq.get(edge, 0)
            if seq <= applied:
                return self._ack(edge)
            if seq > applied + 1:
                if not resync:
                    return {"replay_from": applied}
                self._edge_seq[edge] = seq - 1  # accept the hole
            self._edge_seq[edge] = seq
        if self.kind == "map":
            self._emit(self.fn(item), key)
        elif self.kind == "flat_map":
            for out in self.fn(item):
                self._emit(out, key)
        elif self.kind == "filter":
            if self.fn(item):
                self._emit(item, key)
        elif self.kind == "key_by":
            self._emit(item, self.fn(item))
        elif self.kind == "reduce":
            if key in self._state:
                self._state[key] = self.fn(self._state[key], item)
            else:
                self._state[key] = item
            self._emit((key, self._state[key]), key)
        elif self.kind == "window":
            # Count-based tumbling window: buffer `window_size` items
            # per key, emit one aggregate per full window.
            buf = self._windows.setdefault(key, [])
            buf.append(item)
            if len(buf) >= self._window_size:
                self._windows[key] = []
                out = self.fn(buf) if self.fn else buf
                self._emit((key, out) if key is not None else out, key)
        elif self.kind == "sink":
            self._sink.append(self.fn(item) if self.fn else item)
        self._since_ckpt += 1
        return self._ack(edge)

    def _ack(self, edge):
        """Checkpointing ON: the sender may retire an item only once a
        checkpoint covers it. OFF: applied == covered (no retention —
        plain at-least-once of the credit window)."""
        if edge is None:
            return 0
        if self._ckpt_dir is None:
            return self._edge_seq.get(edge, 0)
        return self._ckpt_edge_seq.get(edge, 0)

    def _emit(self, item, key):
        if not self._senders:
            return
        if key is not None:
            # Stable cross-process hash: Python's hash() is salted per
            # process, which would scatter one key over partitions.
            i = _stable_hash(key) % len(self._senders)
        else:
            i = self._rr
            self._rr = (self._rr + 1) % len(self._senders)
        self._senders[i].push(item, key)

    # -- control ---------------------------------------------------------
    def flush(self):
        """Recursive barrier riding the data channels: this call is
        ordered after every push its caller made, and it returns only
        when the whole downstream DAG has flushed — so when the DRIVER's
        flush of the source stage returns, every item has fully
        propagated (the reference's channel flush semantics). Drains
        this instance's own credit windows first so a downstream death
        replays them before the barrier passes."""
        for s in self._senders:
            s.drain_all()
        if self.downstream:
            flush_with_retry(self.downstream)
        return "ok"

    def sink_values(self):
        return list(self._sink)

    def reduce_state(self):
        return dict(self._state)

    # -- Checkpointable (actor.py:186) — active iff checkpoint_dir ----
    def should_checkpoint(self, checkpoint_context):
        if self._ckpt_dir is None \
                or self._since_ckpt < self._ckpt_interval:
            return False
        self._since_ckpt = 0
        return True

    def save_checkpoint(self, actor_id, checkpoint_id):
        import os
        import pickle
        os.makedirs(self._ckpt_dir, exist_ok=True)
        path = os.path.join(self._ckpt_dir, checkpoint_id)
        with open(path + ".tmp", "wb") as f:
            pickle.dump({
                "state": self._state, "sink": self._sink,
                "windows": self._windows, "rr": self._rr,
                "edge_seq": dict(self._edge_seq),
                # The senders' outgoing retention IS state: coverage of
                # this checkpoint will let the UPSTREAM trim its own
                # retention of our inputs, so outputs not yet covered
                # downstream must be durable HERE or a crash drops them
                # (review finding r5: mid-pipeline loss).
                "senders": [{
                    "seq": s.seq,
                    "covered": s.covered,
                    "retired": list(s.retired),
                    "inflight": [(item, key, seq) for _, item, key, seq
                                 in s.inflight],
                } for s in self._senders],
            }, f)
        os.replace(path + ".tmp", path)
        # Only NOW is this state durable: advance the coverage acks
        # report (upstream retention trims against it).
        self._ckpt_edge_seq = dict(self._edge_seq)

    def load_checkpoint(self, actor_id, available_checkpoints):
        import os
        import pickle
        if self._ckpt_dir is None:
            return None
        for cp in available_checkpoints:  # newest first
            path = os.path.join(self._ckpt_dir, cp.checkpoint_id)
            if os.path.exists(path):
                with open(path, "rb") as f:
                    data = pickle.load(f)
                self._state = data["state"]
                self._sink = data["sink"]
                self._windows = data.get("windows", {})
                self._rr = data.get("rr", 0)
                self._edge_seq = dict(data.get("edge_seq", {}))
                self._ckpt_edge_seq = dict(self._edge_seq)
                for s, saved in zip(self._senders,
                                    data.get("senders", [])):
                    s.seq = saved["seq"]
                    s.covered = saved["covered"]
                    s.retired = deque(saved["retired"])
                    # Pushes that were UNACKED at checkpoint time died
                    # with the old process; re-push them now (the
                    # downstream dedups any it already applied).
                    s.inflight = deque(
                        (s.handle.process.remote(item, key, seq,
                                                 s.edge_id),
                         item, key, seq)
                        for item, key, seq in saved["inflight"])
                return cp.checkpoint_id
        return None

    def checkpoint_expired(self, actor_id, checkpoint_id):
        import os
        if self._ckpt_dir is None:
            return
        try:
            os.unlink(os.path.join(self._ckpt_dir, checkpoint_id))
        except FileNotFoundError:
            pass


def flush_with_retry(handles, timeout_s: float = 30.0):
    """Barrier over possibly-restarting downstream actors: a flush that
    dies mid-restart is retried until the actor returns or the
    redelivery budget is exhausted. The get is UNBOUNDED — a slow flush
    through a backpressured pipeline is not a failure (same contract as
    `EdgeSender.drain_oldest`); `timeout_s` only limits death-retrying."""
    deadline = None
    pending = list(handles)
    while pending:
        try:
            ray_tpu.get([h.flush.remote() for h in pending])
            return
        except (ActorDiedError, ActorUnavailableError):
            now = time.monotonic()
            if deadline is None:
                deadline = now + timeout_s
            elif now > deadline:
                raise
            time.sleep(0.2)


class DataStream:
    def __init__(self, ctx: "StreamingContext", stages: List[dict]):
        self._ctx = ctx
        self._stages = stages

    def _with(self, kind: str, fn: Optional[Callable],
              parallelism: int = 1) -> "DataStream":
        return DataStream(self._ctx, self._stages + [
            {"kind": kind, "fn": fn, "parallelism": parallelism}])

    def map(self, fn, parallelism: int = 1):
        return self._with("map", fn, parallelism)

    def flat_map(self, fn, parallelism: int = 1):
        return self._with("flat_map", fn, parallelism)

    def filter(self, fn, parallelism: int = 1):
        return self._with("filter", fn, parallelism)

    def key_by(self, fn, parallelism: int = 1):
        return self._with("key_by", fn, parallelism)

    def reduce(self, fn, parallelism: int = 1):
        return self._with("reduce", fn, parallelism)

    def window_count(self, size: int, agg_fn: Optional[Callable] = None,
                     parallelism: int = 1):
        """Count-based tumbling window: every `size` items (per key
        after a key_by) emit `agg_fn(items)` (default: the item list)."""
        stream = self._with("window", agg_fn, parallelism)
        stream._stages[-1]["window_size"] = int(size)
        return stream

    def sum(self, parallelism: int = 1):
        return self.reduce(lambda a, b: a + b, parallelism)

    def sink(self, fn: Optional[Callable] = None):
        return self._with("sink", fn, 1)

    def execute(self) -> "ExecutionGraph":
        return self._ctx._execute(self._stages)


class ExecutionGraph:
    """A materialized pipeline (parity: `streaming.py:46`)."""

    def __init__(self, stage_actors: List[List], source_items,
                 credits: int = None):
        self.stage_actors = stage_actors
        self._source_items = source_items
        self._credits = max(1, credits if credits is not None
                            else _default_credits())
        # Source senders persist across run() calls: edge seqs must
        # keep increasing or a second run()'s items would dedup away
        # as replays (review finding r5).
        self._source_senders = [
            EdgeSender(a, f"src->s{j}", self._credits)
            for j, a in enumerate(self.stage_actors[0])]

    def run(self):
        """Push every source item through, then flush the DAG. The
        source loop itself respects the credit window: a slow sink
        stalls THIS loop, not an unbounded in-cluster queue. A stage
        instance dying mid-run is redelivered to after restart
        (module doc). Calling run() again re-pushes the source items
        as NEW occurrences (fresh seqs)."""
        first = self.stage_actors[0]
        for i, item in enumerate(self._source_items):
            self._source_senders[i % len(first)].push(item)
        for s in self._source_senders:
            s.drain_all()
        flush_with_retry(first)
        return self

    def sink_values(self) -> List:
        out = []
        for a in self.stage_actors[-1]:
            out.extend(ray_tpu.get(a.sink_values.remote()))
        return out

    def reduce_state(self) -> Dict:
        merged: Dict = {}
        for stage in self.stage_actors:
            for a in stage:
                merged.update(ray_tpu.get(a.reduce_state.remote()))
        return merged


class StreamingContext:
    def __init__(self, credits: int = None,
                 max_operator_restarts: int = None,
                 checkpoint_dir: str = None,
                 checkpoint_interval: int = 100):
        restarts = (max_operator_restarts
                    if max_operator_restarts is not None
                    else _config.get(
                        "RAY_TPU_STREAMING_OPERATOR_RESTARTS"))
        self._cls = ray_tpu.remote(_OperatorActor).options(
            max_restarts=restarts)
        self._credits = max(1, credits if credits is not None
                            else _default_credits())
        self._checkpoint_dir = checkpoint_dir
        self._checkpoint_interval = checkpoint_interval

    def from_collection(self, items) -> DataStream:
        self._items = list(items)
        return DataStream(self, [])

    def _execute(self, stages: List[dict]) -> ExecutionGraph:
        import cloudpickle
        import os
        # Build actor stages back-to-front so each knows its downstream.
        stage_actors: List[List] = []
        downstream: List = []
        for si, spec in zip(reversed(range(len(stages))),
                            reversed(stages)):
            fn_bytes = cloudpickle.dumps(spec["fn"]) if spec["fn"] \
                else None
            ckpt = None
            if self._checkpoint_dir is not None:
                ckpt = os.path.join(self._checkpoint_dir, f"stage{si}")
            actors = [
                self._cls.remote(spec["kind"], fn_bytes, downstream, i,
                                 self._credits,
                                 checkpoint_dir=ckpt,
                                 checkpoint_interval=(
                                     self._checkpoint_interval),
                                 window_size=spec.get("window_size", 0))
                for i in range(max(1, spec["parallelism"]))]
            stage_actors.insert(0, actors)
            downstream = actors
        if not stage_actors:
            raise ValueError("empty pipeline")
        return ExecutionGraph(stage_actors, self._items, self._credits)
