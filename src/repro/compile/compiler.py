"""``compile_model``: optimized graph -> fixed-shape jitted executables.

This is the software analogue of the paper's code-generation stage: the
optimized IR is lowered ONCE per (backend, batch bucket) into an
ahead-of-time compiled XLA executable.  Serving then only ever *runs*
executables — no shape-polymorphic retracing on the hot path.

    qp  = models.resnet.quantize_params(folded, cfg)        # dict or typed
    cm  = compile_model(cfg, qp, backend="pallas", batch_sizes=(1, 8, 32))
    out = cm(images)          # bucket select + zero-pad + run + slice

Properties:

  * **Weights are closed over once.**  The lowered forward closes over the
    typed parameter pytree; XLA treats the quantized weights as constants of
    the executable, exactly like the FPGA bitstream bakes them into BRAM.
    (The SPMD path passes them as a replicated argument instead: shard_map
    cannot close over arrays placed on a mesh.)
  * **Fixed batch buckets.**  Each size in ``batch_sizes`` gets its own
    executable (compiled lazily on first use, or eagerly with ``eager=True``).
    A batch of n runs on the smallest bucket >= n, zero-padded; batches larger
    than the biggest bucket are chunked.
  * **Donated activation buffers.**  On accelerator backends the input image
    buffer is donated to the executable, so steady-state serving does not
    hold two copies of the activations (no-op on CPU, where XLA does not
    implement donation).
  * **Compile accounting.**  ``trace_counts``/``compile_count`` record every
    (re)trace; tests assert a serving engine ticking forever keeps them at
    one per bucket.
"""
from __future__ import annotations

import functools
import time
from typing import Callable, Dict, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile import lowering
from repro.compile.backends import Backend, get_backend
from repro.compile.params import QResNetParams, ensure_typed
from repro.obs import runtime as _obs
from repro.obs.trace import host_ns


def _donate_argnums():
    # XLA implements buffer donation on TPU/GPU only; donating on CPU just
    # emits a warning per executable.
    return (0,) if jax.default_backend() in ("tpu", "gpu") else ()


class CompiledModel:
    """A quantized network lowered through one backend into per-bucket
    fixed-shape executables.  Callable: ``logits = cm(images)``.

    ``tuning`` (optional) maps lowering task keys (``"stem"``,
    ``"block{i}"``) to :class:`~repro.tune.KernelConfig`; it is stamped onto
    the optimized graph before lowering, so every executable of this model
    runs the tuned tiling."""

    def __init__(self, cfg, params: QResNetParams, backend: Backend,
                 batch_sizes: Sequence[int], tuning=None):
        if not batch_sizes:
            raise ValueError("need at least one batch bucket")
        if any(b <= 0 for b in batch_sizes):
            raise ValueError(f"batch buckets must be positive: {batch_sizes}")
        self.cfg = cfg
        self.params = params
        self.backend = backend
        self.batch_sizes = tuple(sorted(set(int(b) for b in batch_sizes)))
        self.tuning = dict(tuning) if tuning else None
        # LM configs serve token batches, conv configs image batches: the
        # input contract (shape + dtype) is decided once here and every
        # executable path (default/device/shard) lowers from it
        self._is_lm = lowering._is_lm_cfg(cfg)
        self._in_dtype = jnp.int32 if self._is_lm else jnp.float32
        self.graph = lowering.annotate_tuning(
            lowering.optimized_graph(cfg), self.tuning)
        self._forward = backend.lower(self.graph, cfg, params)
        self._donate = bool(_donate_argnums())
        self._execs: Dict[int, Callable] = {}
        self._dev_execs: Dict[tuple, Callable] = {}
        self._shard_execs: Dict[tuple, Callable] = {}
        self._shard_params: Dict[tuple, QResNetParams] = {}
        self.trace_counts: Dict[int, int] = {}
        self.compile_count = 0

    # -- compilation --------------------------------------------------------

    def _staged(self, images):
        # runs at trace time only; the count is the retrace detector
        bs = images.shape[0]
        n = self.trace_counts[bs] = self.trace_counts.get(bs, 0) + 1
        ob = _obs.active()
        if ob is not None:
            ob.metrics.counter(
                "compile_traces_total", "per-bucket trace events").inc(
                    bucket=str(bs), backend=self.backend.name)
            if n > 1:
                # a bucket tracing twice means an executable was rebuilt —
                # the regression the AOT bucket discipline exists to prevent
                ob.metrics.counter(
                    "compile_retraces_total",
                    "per-bucket retraces (should stay 0 in serving)").inc(
                        bucket=str(bs), backend=self.backend.name)
                ob.trace.instant("retrace", cat="compile", track="compile",
                                 bucket=bs, backend=self.backend.name)
        return self._forward(images)

    def _note_compile(self, kind: str, bucket: int, wall_s: float) -> None:
        """Record one XLA compile in the active obs session.  The event
        timestamp is in the session's clock domain (deterministic under
        FakeClock); the measured compile time travels as the volatile
        ``wall_us`` arg."""
        ob = _obs.active()
        if ob is None:
            return
        ob.trace.instant("compile", cat="compile", track="compile",
                         kind=kind, bucket=bucket, backend=self.backend.name,
                         wall_us=round(wall_s * 1e6, 1))
        ob.metrics.counter(
            "compile_executables_total", "AOT executables built").inc(
                kind=kind, bucket=str(bucket), backend=self.backend.name)

    def input_spec(self, batch: int, sharding=None) -> jax.ShapeDtypeStruct:
        """THE input-shape contract of every executable this model compiles
        (default, per-device, and SPMD placements all lower from here):
        ``(batch, img, img, 3) float32`` images for conv configs,
        ``(batch, seq_len) int32`` token batches for LM configs."""
        if self._is_lm:
            return jax.ShapeDtypeStruct(
                (batch, self.cfg.seq_len), jnp.int32, sharding=sharding)
        return jax.ShapeDtypeStruct(
            (batch, self.cfg.img, self.cfg.img, 3), jnp.float32,
            sharding=sharding)

    def executable(self, batch: int) -> Callable:
        """The AOT-compiled executable for one bucket (compiled on first use,
        then reused for the model's lifetime)."""
        if batch not in self.batch_sizes:
            raise ValueError(
                f"batch {batch} is not a compiled bucket {self.batch_sizes}")
        if batch not in self._execs:
            t0 = time.perf_counter()
            jitted = jax.jit(self._staged, donate_argnums=_donate_argnums())
            self._execs[batch] = jitted.lower(self.input_spec(batch)).compile()
            self.compile_count += 1
            self._note_compile("default", batch, time.perf_counter() - t0)
        return self._execs[batch]

    def warmup(self) -> "CompiledModel":
        """Eagerly compile every bucket."""
        for b in self.batch_sizes:
            self.executable(b)
        return self

    # -- placement (replica pools / sharded serving) ------------------------

    def device_executable(self, batch: int, device) -> Callable:
        """The AOT executable for one bucket pinned to ``device``.

        This is how a replica pool instantiates the model per-device: the
        lowering (graph walk + backend closure) is shared, only the XLA
        compile is per-device, and the closed-over weights materialize on
        that device as executable constants — each replica holds its own
        full weight copy, like each replicated FPGA pipeline holds its
        weights in its own BRAM."""
        if batch not in self.batch_sizes:
            raise ValueError(
                f"batch {batch} is not a compiled bucket {self.batch_sizes}")
        key = (int(batch), device)
        if key not in self._dev_execs:
            t0 = time.perf_counter()
            jitted = jax.jit(self._staged, donate_argnums=_donate_argnums())
            spec = self.input_spec(
                batch, sharding=jax.sharding.SingleDeviceSharding(device))
            self._dev_execs[key] = jitted.lower(spec).compile()
            self.compile_count += 1
            self._note_compile("device", batch, time.perf_counter() - t0)
        return self._dev_execs[key]

    def run_placed(self, images, device) -> jnp.ndarray:
        """``__call__`` pinned to one device: the shared batching discipline
        plus a device_put.  Bit-exact with the default path — placement
        never changes the arithmetic."""
        def place(imgs, padded):
            placed = jax.device_put(imgs, device)
            if self._donate and not padded:
                # same donation guard as __call__.  The copy must be
                # unconditional: device_put of an array already committed to
                # `device` returns a NEW object aliasing the SAME buffer, so
                # no identity/no-op check can detect the caller's buffer
                placed = jnp.array(placed, copy=True)
            return placed

        return self._run_batched(
            images, self.batch_sizes, place,
            lambda bucket: self.device_executable(bucket, device))

    def shard_executable(self, mesh, batch: int, axis: str = "data"):
        """One SPMD executable over ``mesh``: the batch dim sharded over
        ``axis`` via shard_map, weights replicated onto every mesh device
        (``parallel.sharding.replicated_shardings``) and passed in as a
        ``P()`` argument.  Returns ``images -> logits``.  ``batch`` must
        divide evenly over the axis.  This is the synchronized data-parallel
        path — one program, all replicas in lockstep — as opposed to the
        replica pool's independent per-device executables."""
        from jax import shard_map
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.parallel.sharding import (
            auto_axes, axis_size, replicated_shardings)

        mesh = auto_axes(mesh)
        n_dev = axis_size(mesh, axis)
        if batch not in self.batch_sizes:
            raise ValueError(
                f"batch {batch} is not a compiled bucket {self.batch_sizes}")
        if batch % n_dev != 0:
            raise ValueError(
                f"bucket {batch} must be divisible by mesh axis "
                f"{axis!r} (size {n_dev})")
        devs = tuple(np.asarray(mesh.devices).flat)
        # the mesh's axis structure is part of the key: two meshes over the
        # same device set (e.g. 4x1 'data' vs 2x2 'data','model') compile
        # different input shardings
        key = (int(batch), axis, tuple(mesh.shape.items()), devs)
        if key not in self._shard_execs:
            # the weight broadcast depends only on the mesh, not the
            # bucket: place once per mesh, share across buckets
            lkey = (axis, devs)
            if lkey not in self._shard_params:
                self._shard_params[lkey] = jax.device_put(
                    self.params, replicated_shardings(self.params, mesh))
            placed = self._shard_params[lkey]

            def body(params, images):
                return self.backend.lower(self.graph, self.cfg,
                                          params)(images)

            smapped = shard_map(body, mesh=mesh, in_specs=(P(), P(axis)),
                                out_specs=P(axis), check_vma=False)
            t0 = time.perf_counter()
            spec = self.input_spec(
                batch, sharding=NamedSharding(mesh, P(axis)))
            exe = jax.jit(smapped).lower(placed, spec).compile()
            self._shard_execs[key] = functools.partial(exe, placed)
            self.compile_count += 1
            self._note_compile("shard", batch, time.perf_counter() - t0)
        return self._shard_execs[key]

    def run_sharded(self, images, mesh, axis: str = "data") -> jnp.ndarray:
        """Run one batch through the SPMD executable with the shared bucket
        discipline, restricted to buckets that divide over the mesh axis.
        Bounded executable count, no shape-polymorphic recompiles on the
        serving path.  (The SPMD executable does not donate its input, so
        no copy guard is needed here.)"""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from repro.parallel.sharding import auto_axes, axis_size

        mesh = auto_axes(mesh)
        n_dev = axis_size(mesh, axis)
        fits = [b for b in self.batch_sizes if b % n_dev == 0]
        if not fits:
            raise ValueError(
                f"no compiled bucket in {self.batch_sizes} divides over "
                f"mesh axis {axis!r} (size {n_dev})")

        def place(imgs, padded):
            return jax.device_put(imgs, NamedSharding(mesh, P(axis)))

        return self._run_batched(
            images, fits, place,
            lambda bucket: self.shard_executable(mesh, bucket, axis))

    # -- dispatch -----------------------------------------------------------

    def bucket_for(self, n: int) -> int:
        """Smallest compiled bucket >= n (the largest bucket if n exceeds
        every bucket — the caller chunks in that case)."""
        for b in self.batch_sizes:
            if b >= n:
                return b
        return self.batch_sizes[-1]

    def _run_batched(self, images, buckets, place, executable) -> jnp.ndarray:
        """THE one home for the serving batching discipline, shared by
        ``__call__``/``run_placed``/``run_sharded``: select the smallest
        bucket >= n from ``buckets``, zero-pad up to it, chunk batches
        beyond the largest bucket, slice the pad rows off the logits.
        ``place(imgs, padded)`` puts one full bucket where
        ``executable(bucket)`` runs it."""
        ob = _obs.active()
        t0 = host_ns() if ob is not None else 0
        images = jnp.asarray(images, self._in_dtype)
        n = images.shape[0]
        if n == 0:
            raise ValueError("empty batch")
        cap = buckets[-1]
        if n > cap:
            outs = [self._run_batched(images[i:i + cap], buckets, place,
                                      executable)
                    for i in range(0, n, cap)]
            return jnp.concatenate(outs, axis=0)
        bucket = next(b for b in buckets if b >= n)
        if n < bucket:
            images = jnp.concatenate(
                [images, jnp.zeros((bucket - n,) + images.shape[1:],
                                   images.dtype)], axis=0)
        placed = place(images, n < bucket)
        t1 = host_ns() if ob is not None else 0
        out = executable(bucket)(placed)[:n]
        if ob is not None:
            # host time only: the launch returns before the device is done
            # (the engines' engine.wait span holds the device's share); an
            # engine claims both spans for its batch (Trace.host_claim)
            ob.trace.host_span("model.put", t0, t1)
            ob.trace.host_span("model.launch", t1, host_ns())
            ob.metrics.counter(
                "model_runs_total", "bucket executions dispatched").inc(
                    bucket=str(bucket), backend=self.backend.name)
            if n < bucket:
                ob.metrics.counter(
                    "model_pad_rows_total",
                    "zero-pad rows added by bucket rounding").inc(
                        bucket - n, bucket=str(bucket),
                        backend=self.backend.name)
        return out

    def __call__(self, images) -> jnp.ndarray:
        def place(imgs, padded):
            if self._donate and not padded:
                # the executable donates its input buffer; never hand it
                # the caller's array (the padded branch made a fresh one)
                return jnp.array(imgs, copy=True)
            return imgs

        return self._run_batched(images, self.batch_sizes, place,
                                 self.executable)

    # -- introspection ------------------------------------------------------

    def stats(self) -> dict:
        return dict(backend=self.backend.name,
                    batch_sizes=self.batch_sizes,
                    compiled=sorted(self._execs),
                    placed=sorted((b, str(d)) for b, d in self._dev_execs),
                    compile_count=self.compile_count,
                    trace_counts=dict(self.trace_counts),
                    tuning={t: c.to_dict()
                            for t, c in sorted(self.tuning.items())}
                    if self.tuning else None)

    def __repr__(self):
        return (f"CompiledModel({self.cfg.name}, backend={self.backend.name!r}, "
                f"buckets={self.batch_sizes}, compiled={sorted(self._execs)})")


def _resolve_tuning(cfg, params, backend_name, batch_sizes, tune):
    """Normalize the ``tune`` argument of :func:`compile_model` into a
    task->KernelConfig dict (or None).  Accepted forms:

      * ``None`` / ``False``   — untuned (the default tiling).
      * a dict                 — an explicit per-task assignment (the format
                                 ``tune.search`` returns / the cache stores).
      * a ``TuneResult``       — its ``.tuning``.
      * ``"auto"``             — cache hit or run the full two-stage search.
      * ``"analytic"``         — cost-model stage only (no device timing).
      * ``"device"``           — force a fresh two-stage search (still
                                 written back to the cache).
    """
    if not tune:
        return None
    if hasattr(tune, "tuning"):          # TuneResult without importing it
        return tune.tuning
    if isinstance(tune, dict):
        # normalize cache-style {"task": {"knob": v}} entries to KernelConfig
        # so stats()/engine introspection sees one type
        from repro.tune.config import KernelConfig
        return {task: c if isinstance(c, KernelConfig)
                else KernelConfig.from_dict(c)
                for task, c in tune.items()}
    if isinstance(tune, str):
        from repro import tune as T      # lazy: repro.tune imports us
        if tune not in ("auto", "analytic", "device"):
            raise ValueError(
                f"tune={tune!r}: expected a task->KernelConfig dict, a "
                f"TuneResult, or one of 'auto'/'analytic'/'device'")
        if lowering._is_lm_cfg(cfg):
            raise ValueError(
                f"tune={tune!r}: the search modes cover conv configs only; "
                f"pass an explicit task->KernelConfig dict for LM config "
                f"{cfg.name!r} (spaces: tune.space.lm_model_space)")
        res = T.search(cfg, params, backend=backend_name,
                       batch=max(batch_sizes),
                       device=tune != "analytic",
                       use_cache=tune != "device")
        return res.tuning
    raise TypeError(f"unsupported tune argument: {type(tune).__name__}")


def compile_model(cfg, qparams, backend: Union[str, Backend] = "pallas",
                  batch_sizes: Sequence[int] = (1, 8, 32),
                  eager: bool = False, tune=None) -> CompiledModel:
    """Lower the optimized graph of ``cfg`` through ``backend`` into a
    :class:`CompiledModel` with one fixed-shape executable per batch bucket.

    ``qparams`` may be the legacy ``quantize_params`` dict or a typed
    :class:`QResNetParams`; ``backend`` a registered name or an instance.
    ``tune`` selects the kernel tiling: a per-task dict / ``TuneResult`` from
    ``repro.tune``, or ``"auto"``/``"analytic"``/``"device"`` to run the
    search here (see :func:`_resolve_tuning`).
    """
    params = ensure_typed(qparams)
    be = get_backend(backend) if isinstance(backend, str) else backend
    tuning = _resolve_tuning(cfg, params, be.name, batch_sizes, tune)
    cm = CompiledModel(cfg, params, be, batch_sizes, tuning=tuning)
    if eager:
        cm.warmup()
    return cm


def lower_forward(cfg, qparams, backend: Union[str, Backend]) -> Callable:
    """Un-bucketed lowering: the backend's ``images -> logits`` callable with
    no jit wrapper.  This is what the thin ``models.resnet`` compatibility
    wrappers (``int_forward``/``pallas_forward``) call."""
    params = ensure_typed(qparams)
    be = get_backend(backend) if isinstance(backend, str) else backend
    return be.lower(lowering.optimized_graph(cfg), cfg, params)
