"""Batched serving engines: continuous batching over a fixed slot set.

``Engine`` serves the LM workload: requests (prompts) are admitted into free
slots; one jitted ``decode_step`` advances every active slot per tick (one
token each).  Finished slots are recycled immediately — the dataflow analogue
of the paper's stall-free pipeline: no slot waits for the longest request in
a "batch".  Prefill is per-request (token-by-token through the cache for
simplicity at test scale; the prefill_32k cell exercises the real batched
prefill path).

``ResNetEngine`` serves the paper's own workload — integer ResNet8/20 image
classification — entirely through ``repro.compile.CompiledModel``: the
optimized graph is lowered once per (backend, batch bucket) into fixed-shape
AOT executables, with the fused Pallas pipeline as the default backend, so
serving traffic takes the minimum-HBM-traffic path with zero per-tick
retracing.
"""
from __future__ import annotations

import asyncio
import collections
import dataclasses
from typing import List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import model as M
from repro.obs import runtime as _obs
from repro.obs.trace import host_ns
from repro.serve import sched as S

# backends whose logits must agree BITWISE with each other: a nonzero A/B
# deviation between two of these is an arithmetic bug, not quantization
# error, and trips the health monitor's bit-exactness sentinel.  The float
# shadow legitimately deviates and never counts as a mismatch.
_INT_BACKENDS = frozenset({"pallas", "pallas-stream", "lax-int", "int"})


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new: int
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


class Engine:
    def __init__(self, cfg, params, slots: int = 4, max_len: int = 256,
                 eos: Optional[int] = None):
        self.cfg, self.params = cfg, params
        self.slots, self.max_len, self.eos = slots, max_len, eos
        self.cache = M.init_cache(cfg, slots, max_len)
        self.pos = np.zeros((slots,), np.int32)
        self.active: List[Optional[Request]] = [None] * slots
        self.queue: List[Request] = []
        self.last_tok = np.zeros((slots, 1), np.int32)

        self._step = jax.jit(
            lambda p, t, pos, c: M.decode_step(p, cfg, t, pos, c))

    def submit(self, req: Request):
        self.queue.append(req)

    def _admit(self):
        for i in range(self.slots):
            if self.active[i] is None and self.queue:
                req = self.queue.pop(0)
                self.active[i] = req
                if not req.prompt:
                    # empty prompt: nothing to prefill (and no logits to seed
                    # from) — start decoding from token 0 at position 0 on
                    # the next tick
                    self.pos[i] = 0
                    self.last_tok[i, 0] = 0
                    continue
                # prefill token-by-token into this slot's cache
                for j, tok in enumerate(req.prompt):
                    t = self.last_tok.copy()
                    t[i, 0] = tok
                    pos = self.pos.copy()
                    pos[i] = j
                    logits, self.cache = self._step(
                        self.params, jnp.asarray(t), jnp.asarray(pos),
                        self.cache)
                self.pos[i] = len(req.prompt)
                self.last_tok[i, 0] = int(np.argmax(
                    np.asarray(logits)[i, 0]))
                req.out.append(int(self.last_tok[i, 0]))

    def tick(self):
        """One decode step for all active slots."""
        self._admit()
        if not any(r is not None for r in self.active):
            return False
        logits, self.cache = self._step(
            self.params, jnp.asarray(self.last_tok), jnp.asarray(self.pos),
            self.cache)
        nxt = np.argmax(np.asarray(logits)[:, 0, :], axis=-1)
        for i, req in enumerate(self.active):
            if req is None:
                continue
            self.pos[i] += 1
            tok = int(nxt[i])
            req.out.append(tok)
            self.last_tok[i, 0] = tok
            if len(req.out) >= req.max_new or tok == self.eos or \
                    self.pos[i] >= self.max_len - 1:
                req.done = True
                self.active[i] = None
                self.pos[i] = 0
                self.last_tok[i, 0] = 0
        return True

    def run(self, max_ticks: int = 10_000):
        done = []
        ticks = 0
        while (self.queue or any(r is not None for r in self.active)) and \
                ticks < max_ticks:
            self.tick()
            ticks += 1
        return ticks


# ---------------------------------------------------------------------------
# Image-classification serving over the fused Pallas integer pipeline
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ImageRequest:
    rid: int
    image: np.ndarray                     # (H, W, 3) float image, or an LM
                                          # (seq_len,) int token vector
    logits: Optional[np.ndarray] = None   # (num_classes | vocab,) once served
    label: Optional[int] = None
    done: bool = False


def _bucket_sizes(batch: int, batch_sizes) -> tuple:
    """An engine's buckets (default: its max batch alone), holding it."""
    sizes = (batch,) if batch_sizes is None else batch_sizes
    if batch not in sizes:
        raise ValueError(
            f"max batch {batch} must be one of batch_sizes {sizes}")
    return sizes


class _Batch(NamedTuple):
    """A launched batch: its requests, the staging buffer whose first
    ``len(reqs)`` rows hold their images, and its logits on the device."""
    reqs: List[ImageRequest]
    buf: np.ndarray
    out: jax.Array


class _BatchPath:
    """One device's batch path, under both image engines: gather, launch,
    wait and fetch, fan out.  ``run`` launches a batch without waiting (on
    the engine's model, or on one replica of its pool); ``spec``, the
    model's ``input_spec`` at the engine's max batch, is the input contract.

    A fresh array of a whole batch (154 MB of f32 224x224 images at 256) is
    a new ``mmap`` whose every page faults on first write, so batches are
    gathered into buffers of ``spec``'s shape, each faulted in once when
    allocated.  A batch holds its buffer until its fan-out: the device may
    read it until then (on the CPU ``jnp.asarray`` may alias it), and so
    may ``ResNetEngine``'s shadows.  Freed buffers take turns on a FIFO
    list; one is allocated only when none is free."""

    def __init__(self, run, spec: jax.ShapeDtypeStruct):
        self._run = run
        self.shape, self.dtype = tuple(spec.shape), np.dtype(spec.dtype)
        self._free: collections.deque = collections.deque()

    def check(self, req: ImageRequest) -> None:
        """Reject a payload no fixed-shape executable can batch, at submit,
        so that the engines' loops stay total."""
        shape = tuple(np.shape(req.image))
        if shape != self.shape[1:]:
            raise ValueError(
                f"request {req.rid}: payload shape {shape} does not match "
                f"the compiled input shape {self.shape[1:]}")

    def launch(self, reqs: List[ImageRequest], bid: int, ob,
               t_start: int) -> _Batch:
        """Gather ``reqs`` into a staging buffer and launch them.  Under a
        live obs session ``engine.gather`` (from the caller's ``t_start``)
        and the model's ``model.put``/``model.launch`` carry ``bid``, and
        ``engine_staging_reuses_total`` counts the launches that refilled
        a buffer an earlier batch had filled."""
        reused = bool(self._free)
        if reused:
            buf = self._free.popleft()
        else:
            buf = np.empty(self.shape, self.dtype)
            buf.fill(0)                   # fault every page in now
        imgs = np.stack([np.asarray(r.image, buf.dtype) for r in reqs],
                        out=buf[:len(reqs)])
        if ob is not None:
            t_gather = host_ns()
            mark = ob.trace.host_mark()
        out = self._run(imgs)
        if ob is not None:
            ob.trace.host_claim(mark, bid)    # the model's put and launch
            ob.trace.host_span("engine.gather", t_start, t_gather, bid)
            if reused:
                ob.metrics.counter(
                    "engine_staging_reuses_total",
                    "launches that filled an already-faulted staging "
                    "buffer").inc()
        return _Batch(reqs, buf, out)

    def fetch(self, b: _Batch, bid: int, ob) -> np.ndarray:
        """Wait for batch ``b``'s logits and read them back (``engine.wait``,
        ``engine.fetch``, carrying ``bid``); a failed batch frees its buffer
        before the error surfaces."""
        t_wait = host_ns() if ob is not None else 0
        try:
            jax.block_until_ready(b.out)
            t_ready = host_ns() if ob is not None else 0
            logits = np.asarray(b.out)
        except Exception:
            self._free.append(b.buf)
            raise
        if ob is not None:
            t_fetched, tr = host_ns(), ob.trace
            tr.host_span("engine.wait", t_wait, t_ready, bid)
            tr.host_span("engine.fetch", t_ready, t_fetched, bid)
        return logits

    def fanout(self, b: _Batch, logits: np.ndarray, bid: int, ob) -> None:
        """Give each request its logits, label and done flag
        (``engine.fanout``, carrying ``bid``), then free ``b``'s buffer."""
        t0 = host_ns() if ob is not None else 0
        for i, r in enumerate(b.reqs):
            r.logits = logits[i]
            r.label = int(np.argmax(logits[i]))
            r.done = True
        self._free.append(b.buf)
        if ob is not None:
            ob.trace.host_span("engine.fanout", t0, host_ns(), bid)


class ResNetEngine:
    """Image-classification engine serving entirely through
    :class:`repro.compile.CompiledModel`.

    ``compile_model`` lowers the optimized graph once per (backend, batch
    bucket) into fixed-shape AOT executables; the engine then only *selects a
    bucket, zero-pads, and runs* — no retracing ever happens on a tick (the
    model's ``trace_counts`` stay at 1 per bucket, asserted in
    tests/test_serve.py).  Backends come from the ``repro.compile`` registry:

      * ``pallas`` (default) — the fused integer kernel pipeline (stem kernel
        + one add-fold kernel per residual block).
      * ``lax-int`` (alias ``int``) — the lax reference integer graph,
        bit-identical logits, unfused dataflow.
      * ``float`` — float emulation of the integer graph on the same pow2
        grids, for A/B'ing quantization error in production.

    ``ab_backends`` compiles shadow models on additional backends; every tick
    the primary batch is replayed through each shadow and the max absolute
    logit deviation is recorded in ``ab_stats`` — a live parity probe for
    canarying a new backend against the serving one.

    ``tune`` engages the ``repro.tune`` design-space layer (a per-task
    config dict / TuneResult, or ``"auto"``/``"analytic"``/``"device"``):
    the primary model serves with the tuned kernel tiling, while the
    shadows stay untuned so the A/B probe also guards the tuner.
    """

    def __init__(self, cfg, qparams, batch: int = 8, backend: str = "pallas",
                 batch_sizes=None, ab_backends=(), tune=None):
        from repro.compile import compile_model

        self.cfg, self.batch = cfg, batch
        self.backend = backend
        batch_sizes = _bucket_sizes(batch, batch_sizes)
        # ``tune`` flows straight into compile_model: a per-task dict /
        # TuneResult from repro.tune, or "auto"/"analytic"/"device".  Tuning
        # only reschedules the kernels — logits are bit-identical — so the
        # shadows stay untuned: the A/B probe then also guards the tuner.
        self.model = compile_model(cfg, qparams, backend=backend,
                                   batch_sizes=batch_sizes, tune=tune)
        self.tuning = self.model.tuning
        self.qparams = self.model.params
        self.shadows = {name: compile_model(cfg, qparams, backend=name,
                                            batch_sizes=batch_sizes)
                        for name in ab_backends}
        self.ab_stats = {name: [] for name in self.shadows}
        self.queue: List[ImageRequest] = []
        self.served = 0
        self.ticks = 0                    # batches served (the batch id)
        # runs ``self.model`` as it is at each launch, so a model swapped
        # in after construction serves
        self._path = _BatchPath(lambda imgs: self.model(imgs),
                                self.model.input_spec(batch))
        # launched, not completed, oldest first: ids ticks, ticks + 1
        self._in_flight: collections.deque = collections.deque()

    def submit(self, req: ImageRequest):
        """Enqueue one request (shape-validated at admission)."""
        self._path.check(req)
        self.queue.append(req)

    def tick(self) -> bool:
        """Complete one batch, keeping the next one in flight behind it;
        returns False once the queue and the in-flight batches are empty.

        A one-deep software pipeline: with nothing in flight the tick first
        launches a batch, then launches the next one while the queue holds
        requests, and only then blocks on the oldest in-flight batch.  JAX
        dispatches asynchronously, so the next batch's input copy and
        forward queue behind the current one on the device while the host
        reads back, fans out and refills.  Requests complete in FIFO order.

        Under a live obs session ``engine.tick`` spans the launches (see
        ``_launch``) and the completion of the oldest batch:
        ``engine.wait`` (the device), ``engine.fetch`` (readback) and
        ``engine.fanout`` (per-request results).  These four carry the id
        of the batch the tick completes."""
        if not self.queue and not self._in_flight:
            return False
        ob = _obs.active()
        t0 = host_ns() if ob is not None else 0
        if not self._in_flight:
            self._launch(ob)
        if self.queue:
            self._launch(ob)
        b, bid = self._in_flight.popleft(), self.ticks
        logits = self._path.fetch(b, bid, ob)
        imgs = b.buf[:len(b.reqs)]
        for name, shadow in self.shadows.items():
            dev = np.max(np.abs(np.asarray(shadow(imgs)) - logits))
            self.ab_stats[name].append(float(dev))
            if ob is not None:
                ob.metrics.counter(
                    "ab_checks_total", "A/B shadow replays").inc(shadow=name)
                ob.metrics.gauge(
                    "ab_max_abs_dev",
                    "last max |shadow - primary| logit deviation").set(
                        float(dev), shadow=name)
                if dev > 0 and self.backend in _INT_BACKENDS \
                        and name in _INT_BACKENDS:
                    ob.metrics.counter(
                        "ab_mismatch_total",
                        "integer shadow disagreed bitwise with primary").inc(
                            shadow=name)
        self._path.fanout(b, logits, bid, ob)
        self.served += len(b.reqs)
        if ob is not None:
            ob.trace.host_span("engine.tick", t0, host_ns(), bid)
        self.ticks += 1
        return True

    def _launch(self, ob) -> None:
        """Launch up to ``batch`` requests off the queue.  Under a live obs
        session ``engine_overlapped_launches_total`` counts the launches
        made while an earlier batch was still in flight."""
        t0 = host_ns() if ob is not None else 0
        reqs = self.queue[:self.batch]
        del self.queue[:len(reqs)]
        b = self._path.launch(reqs, self.ticks + len(self._in_flight), ob,
                              t0)
        if ob is not None and self._in_flight:
            ob.metrics.counter(
                "engine_overlapped_launches_total",
                "batches launched while an earlier batch was in "
                "flight").inc()
        self._in_flight.append(b)

    def run(self, max_ticks: int = 10_000) -> int:
        """Tick until the queue and the in-flight batches are empty;
        returns the ticks, one a batch."""
        ticks = 0
        while (self.queue or self._in_flight) and ticks < max_ticks:
            self.tick()
            ticks += 1
        return ticks


# ---------------------------------------------------------------------------
# Scale-out serving: replica pool + deadline-based batch coalescing
# ---------------------------------------------------------------------------


class ShardedResNetEngine:
    """Multi-replica image serving: the ``CompiledModel`` lowered once and
    instantiated per-device (``repro.serve.sched.ReplicaPool``), fed by a
    deadline-based batch coalescer (``repro.serve.sched.Scheduler``).

    Request lifecycle (docs/serving.md has the full diagram):

        submit (arrival stamped, optional deadline/priority)
          -> coalesce (micro-batch held open until a bucket fills or the
             oldest request's slack is exhausted: ``slack_ms`` best-effort
             window, or ``deadline - service_estimate`` with a deadline)
          -> dispatch (least-loaded replica; jax async dispatch, so multiple
             replicas genuinely overlap on multi-device hosts)
          -> harvest (block on results, stamp completion, record queue-wait
             vs compute latency split)

    Each replica runs the batch path of the single-device
    :class:`ResNetEngine` and is bit-exact with it: replication and
    coalescing change *where and when* a batch runs, never the arithmetic
    (asserted in tests/test_serve_sharded.py).

    ``clock`` is injectable (``sched.FakeClock``) so scheduling behavior is
    simulable; ``max_pending`` bounds admission (``submit`` raises
    ``sched.Backpressure`` when full; ``submit_async`` awaits instead).
    """

    def __init__(self, cfg, qparams, batch: int = 8, backend: str = "pallas",
                 replicas: Optional[int] = None, devices=None,
                 batch_sizes=None, slack_ms: float = 5.0, clock=None,
                 max_pending: Optional[int] = None, tune=None,
                 service_estimate_ms: float = 0.0):
        from repro.compile import compile_model

        self.cfg, self.batch = cfg, batch
        self.backend = backend
        # lowered ONCE; the pool only adds per-device XLA compiles
        self.model = compile_model(cfg, qparams, backend=backend,
                                   batch_sizes=_bucket_sizes(
                                       batch, batch_sizes), tune=tune)
        self.tuning = self.model.tuning
        self.pool = S.ReplicaPool(self.model, devices=devices,
                                  replicas=replicas)
        self.sched = S.Scheduler(
            self.pool.replicas, max_batch=batch, slack_s=slack_ms * 1e-3,
            clock=clock, max_pending=max_pending,
            service_estimate_s=service_estimate_ms * 1e-3)
        self.clock = self.sched.clock
        self.served = 0
        spec = self.model.input_spec(batch)
        self._paths = [_BatchPath(lambda imgs, i=i: self.pool.run(i, imgs),
                                  spec) for i in range(len(self.pool))]
        self._in_flight: List[tuple] = []       # (Dispatch, _Batch)

    # -- admission ----------------------------------------------------------

    def submit(self, req: ImageRequest, deadline_ms: Optional[float] = None,
               priority: int = 0) -> S.ScheduledRequest:
        """Admit one request.  ``deadline_ms`` is relative to now; omit it
        for best-effort coalescing under the ``slack_ms`` window.  Raises
        ``sched.Backpressure`` at ``max_pending``."""
        self._paths[0].check(req)             # one contract for every replica
        deadline_in = None if deadline_ms is None else deadline_ms * 1e-3
        return self.sched.submit(req, deadline_in=deadline_in,
                                 priority=priority)

    async def submit_async(self, req: ImageRequest,
                           deadline_ms: Optional[float] = None,
                           priority: int = 0,
                           retry_s: float = 1e-3) -> S.ScheduledRequest:
        """``submit`` with backpressure-as-await: when the pending queue is
        full, yield to the event loop (letting ``run_async`` drain) and
        retry instead of raising."""
        while True:
            try:
                return self.submit(req, deadline_ms=deadline_ms,
                                   priority=priority)
            except S.Backpressure:
                await asyncio.sleep(retry_s)

    # -- dispatch loop ------------------------------------------------------

    @property
    def outstanding(self) -> int:
        return self.sched.outstanding

    def tick(self) -> bool:
        """One scheduler round: dispatch every due micro-batch (async — the
        arrays are not blocked on, so replicas overlap), then harvest all
        in-flight results.  Returns True if any work was done."""
        dispatched = self._dispatch_due()
        harvested = self._harvest()
        return bool(dispatched or harvested)

    def _dispatch_due(self) -> int:
        """Dispatch every due micro-batch.  Under a live obs session each
        records ``engine.dispatch`` around ``sched.poll``, ``engine.gather``
        and the model's ``model.put``/``model.launch``, all carrying the
        dispatch's ``seq``."""
        n = 0
        ob = _obs.active()
        while True:
            t0 = host_ns() if ob is not None else 0
            d = self.sched.poll()
            if d is None:
                break
            t_poll = host_ns() if ob is not None else 0
            b = self._paths[d.replica.index].launch(
                [r.payload for r in d.requests], d.seq, ob, t_poll)
            self._in_flight.append((d, b))
            if ob is not None:
                tr = ob.trace
                tr.host_span("engine.dispatch", t0, host_ns(), d.seq)
                tr.host_span("sched.poll", t0, t_poll, d.seq)
            n += 1
        return n

    def _next_ready_index(self) -> Optional[int]:
        """Index of a dispatch whose result is already materialized, else
        None.  Harvesting ready-first matters twice: blocking strictly FIFO
        would stamp a fast replica's completion with a slow replica's wait
        (inflating compute_ms and the deadline EWMA), and would hold the
        loop hostage to the slowest replica while due batches could be
        dispatching to idle ones."""
        for i, (_, b) in enumerate(self._in_flight):
            is_ready = getattr(b.out, "is_ready", None)
            if is_ready is not None and is_ready():
                return i
        return None

    def _harvest(self, block: bool = True) -> int:
        """Complete every dispatch whose result is ready; when ``block`` and
        nothing at all was ready, wait on the oldest so the caller always
        makes progress.  Returns between harvests as soon as the remainder
        is still computing — the drive loops interleave ``_dispatch_due``
        so idle replicas never wait head-of-line on a slow one.  Under a
        live obs session each harvested dispatch records
        ``engine.harvest`` around ``engine.wait``, ``engine.fetch``,
        ``sched.complete`` and ``engine.fanout``, carrying its ``seq``."""
        n = 0
        ob = _obs.active()
        while self._in_flight:
            t0 = host_ns() if ob is not None else 0
            i = self._next_ready_index()
            if i is None:
                if not block or n > 0:
                    break         # let the caller dispatch more work first
                i = 0             # nothing ready anywhere: wait on the oldest
            d, b = self._in_flight.pop(i)
            path = self._paths[d.replica.index]
            try:
                logits = path.fetch(b, d.seq, ob)
            except Exception:
                # a dispatch whose device execution errored must not jam the
                # head of the line or leak in-flight slots: it is evicted;
                # release the scheduler accounting (its requests stay
                # done=False so callers can see the failure), then surface
                # the error
                self.sched.complete(d, failed=True)
                raise
            t_fetched = host_ns() if ob is not None else 0
            self.sched.complete(d)
            t_complete = host_ns() if ob is not None else 0
            path.fanout(b, logits, d.seq, ob)
            self.served += len(d)
            if ob is not None:
                tr = ob.trace
                tr.host_span("engine.harvest", t0, host_ns(), d.seq)
                tr.host_span("sched.complete", t_fetched, t_complete, d.seq)
            n += 1
        return n

    def run(self, max_ticks: int = 100_000) -> int:
        """Drive until everything admitted so far is served.  When nothing
        is due yet (the coalescer is holding a batch open), sleeps the clock
        to the next dispatch-by time instead of spinning."""
        ticks = 0
        while self.outstanding and ticks < max_ticks:
            if not self.tick():
                self._sleep_until_due()
            ticks += 1
        return ticks

    def _sleep_until_due(self) -> None:
        due_at = self.sched.next_due_at()
        if due_at is None:
            return
        self.clock.sleep(max(due_at - self.clock.now(), 1e-4))

    async def run_async(self, idle_sleep_s: float = 1e-3) -> int:
        """Async dispatch loop: serve until the engine is shut down *and*
        drained.  Producers ``submit``/``submit_async`` concurrently; call
        ``shutdown()`` to let the loop finish the tail and return.  The
        blocking wait on device results runs off the event loop, so
        producers keep filling the next micro-batch during compute."""
        ticks = 0
        while not (self.sched.closed and not self.outstanding
                   and not self._in_flight):
            progressed = self._dispatch_due() > 0
            if self._in_flight:
                progressed |= bool(
                    await asyncio.to_thread(self._harvest))
            if progressed:
                await asyncio.sleep(0)           # yield to producers
            else:
                await asyncio.sleep(idle_sleep_s)
            ticks += 1
        return ticks

    def shutdown(self) -> None:
        """Stop admission; pending requests become due immediately and drain
        through the normal dispatch path (graceful drain)."""
        self.sched.shutdown()

    # -- autoscaling hooks --------------------------------------------------

    @property
    def active_replicas(self) -> int:
        """Replicas currently receiving new dispatches (autoscaler-set)."""
        return self.sched.active

    def set_active_replicas(self, n: int, reason: str = None) -> int:
        """Actuate an autoscaling decision: route new dispatches to the
        first ``n`` replicas only (clamped to the pool size).  Deactivated
        replicas finish their in-flight work and keep their executables
        warm, so scaling back up is instant.  ``reason`` (the policy
        trigger) is recorded on the scheduler's scale-event accounting."""
        return self.sched.set_active(n, reason=reason)

    @property
    def queue_depth(self) -> int:
        """Requests admitted but not yet dispatched — the autoscaler's
        primary pressure signal."""
        return self.sched.pending

    # -- introspection ------------------------------------------------------

    def latency_stats(self) -> dict:
        """p50/p99 queue-wait vs compute split, deadline accounting, and
        per-replica served/dispatched counts."""
        return self.sched.summary()

    def stats(self) -> dict:
        # latency_stats() already carries the per-replica breakdown under
        # 'replicas'; only add what it doesn't have
        return dict(model=self.model.stats(), pool_size=len(self.pool),
                    served=self.served, **self.latency_stats())
