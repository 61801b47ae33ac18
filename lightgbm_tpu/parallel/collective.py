"""Single ``Collective`` interface over the two comm backends.

PAPER.md's blueprint maps the reference's Network layer (src/network/:
Bruck / recursive-halving collectives over TCP sockets) onto *XLA
collectives over ICI*.  This module is that seam made explicit: one
interface for allreduce / allgather / scatter-reduce over histogram and
scalar payloads plus rank/world/fence queries, with two backends:

- ``MeshCollective`` — single-controller, in-process: the grow loop runs
  ``shard_map``'d over a ``jax.sharding.Mesh`` of the local devices and
  exchanges histograms with ``psum``/``all_gather`` that never leave HBM
  (no pickle, no socket hop, no per-collective host sync).  The host
  side of the interface is therefore trivial — host values are already
  global — while the traced side (the primitives below) carries
  trace-time byte attribution so comm counters and ``comm/mesh_psum``
  spans stay populated even though the collectives execute inside one
  fused XLA program.
- ``SocketCollective`` — cross-host: wraps the existing ``SocketComm``/
  ``ElasticComm`` hub-and-spoke wire (parallel/distributed.py) behind
  the same interface, preserving its retry policy, heartbeat liveness
  and generation fencing.  Traced collectives route through an ordered
  host callback (``SocketAxis``), so the SAME grow program serves both
  backends: ``axis_name`` is either a mesh axis string or a
  ``SocketAxis`` handle.

A third backend composes the two: ``HybridCollective``
(parallel/hybrid.py) psums within the host's local mesh and rides the
socket wire between per-host leaders — the topology docs/Distributed.md
names, with whole-host fault domains.

Backend selection rides ``Config.tpu_comm_backend``
(auto|mesh|socket|hybrid); ``make_collective`` resolves it, emits one
``comm_backend`` recorder event per (requested, resolved-topology)
change and falls back socket-ward when the mesh is unavailable (fewer
than two local devices, or the ``mesh_unavailable`` chaos drill) — see
docs/Distributed.md.
"""
from __future__ import annotations

import os
import threading
from contextlib import contextmanager
from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..utils import log

#: the 1-D model-parallel mesh axis every learner shard_maps over
AXIS = "mp"

def shard_mapped(fn, mesh, in_specs, out_specs):
    """jax.shard_map with the varying-manual-axes check off (the grow
    programs mix replicated and sharded values freely)."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# --------------------------------------------------------------------- #
# Traced collective primitives.
#
# Every collective inside the grow programs (ops/grow.py,
# ops/grow_partition.py) goes through these instead of bare jax.lax so
# that (a) the mesh backend can attribute collective bytes at TRACE time
# (the ops execute inside one fused jit program — there is no host
# boundary to measure at), and (b) a SocketAxis handle swaps the XLA
# collective for an ordered host callback into the socket wire without
# touching the grow code.
# --------------------------------------------------------------------- #

_TLS = threading.local()


def _np_dtype(name: str) -> np.dtype:
    """np.dtype by name, resolving the accelerator dtypes (bfloat16 &
    friends) that plain numpy doesn't know through ml_dtypes."""
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes
        return np.dtype(getattr(ml_dtypes, name))


def _leaf_bytes(x) -> int:
    try:
        shape = getattr(x, "shape", ())
        dtype = getattr(x, "dtype", None)
        item = np.dtype(dtype).itemsize if dtype is not None else 4
        return int(np.prod(shape)) * item if shape else item
    except Exception:  # noqa: BLE001 — accounting must never break tracing
        return 0


def _account(kind: str, tree) -> None:
    prof = getattr(_TLS, "profile", None)
    if prof is None:
        return
    nbytes = sum(_leaf_bytes(leaf) for leaf in jax.tree_util.tree_leaves(tree))
    cnt, tot = prof.get(kind, (0, 0))
    prof[kind] = (cnt + 1, tot + nbytes)


@contextmanager
def capture_traced(profile: Dict[str, Tuple[int, int]]):
    """Collect {collective kind: (call count, payload bytes)} for every
    traced primitive executed on this thread while the context is live —
    i.e. during the first (tracing) call of a jitted grow program."""
    prev = getattr(_TLS, "profile", None)
    _TLS.profile = profile
    try:
        yield profile
    finally:
        _TLS.profile = prev


def psum(x, axis):
    """Allreduce-sum over the collective axis (mesh string or SocketAxis)."""
    if isinstance(axis, SocketAxis):
        return axis.allreduce(x, "sum")
    _account("psum", x)
    return jax.lax.psum(x, axis)


def pmax(x, axis):
    """Allreduce-max over the collective axis."""
    if isinstance(axis, SocketAxis):
        return axis.allreduce(x, "max")
    _account("pmax", x)
    return jax.lax.pmax(x, axis)


def all_gather(x, axis, **kwargs):
    """Allgather over the collective axis (new leading world dim)."""
    if isinstance(axis, SocketAxis):
        return axis.gather(x)
    _account("all_gather", x)
    return jax.lax.all_gather(x, axis, **kwargs)


def psum_scatter(x, axis, **kwargs):
    """Scatter-reduce over the collective axis: each rank keeps its own
    shard of the summed payload (ReduceScatter)."""
    if isinstance(axis, SocketAxis):
        return axis.scatter_reduce(x, **kwargs)
    _account("psum_scatter", x)
    return jax.lax.psum_scatter(x, axis, **kwargs)


def axis_index(axis):
    """This shard's rank along the collective axis."""
    if isinstance(axis, SocketAxis):
        # the hybrid axis nests a mesh inside the wire: its shard index
        # is host-major * local-mesh-minor (HybridAxis.global_index)
        gi = getattr(axis, "global_index", None)
        if gi is not None:
            return gi()
        return jnp.int32(axis.rank)
    return jax.lax.axis_index(axis)


# --------------------------------------------------------------------- #
# The interface
# --------------------------------------------------------------------- #

class Collective:
    """Rank/world/fence queries plus host-payload collectives.

    Concrete backends add the traced side: ``MeshCollective`` hands the
    learners its mesh + axis string; ``SocketCollective`` hands them a
    ``SocketAxis`` whose traced ops call back into the wire."""

    backend = "none"

    @property
    def rank(self) -> int:
        raise NotImplementedError

    @property
    def world(self) -> int:
        raise NotImplementedError

    # host-payload collectives (scalars / small numpy arrays)
    def allreduce(self, value, op: str = "sum"):
        raise NotImplementedError

    def allgather(self, payload) -> List:
        raise NotImplementedError

    def scatter_reduce(self, value):
        """Allreduce then keep this rank's equal slice of dim 0."""
        total = self.allreduce(value, "sum")
        arr = np.asarray(total)
        per = arr.shape[0] // max(self.world, 1)
        return arr[self.rank * per:(self.rank + 1) * per]

    # membership / fencing
    def fence(self) -> int:
        """Barrier; returns the generation the world agreed on."""
        raise NotImplementedError

    def generation(self) -> int:
        return 0

    def world_changed(self):
        return None

    def fenced_ranks(self) -> Tuple[int, ...]:
        return ()

    def close(self) -> None:
        pass


class MeshCollective(Collective):
    """In-process shard_map/psum backend over the local devices.

    Single controller: the host process IS every rank, so host-payload
    collectives are identities ([payload] * world for allgather) and
    ``fence`` is free.  The real collectives are the traced primitives
    above, executed inside the jitted grow programs; ``bind`` wraps each
    jitted callable so its traced collective profile (captured once, at
    trace time) is re-emitted as backend-tagged comm counters and one
    ``comm/mesh_psum`` span per dispatch.
    """

    backend = "mesh"

    def __init__(self, num_machines: int, devices=None, axis: str = AXIS,
                 registry=None):
        self.axis = axis
        self._d = int(num_machines)
        devices = (jax.devices() if devices is None
                   else list(devices))[:num_machines]
        if len(devices) < num_machines:
            raise ValueError(
                "mesh backend needs %d devices, found %d"
                % (num_machines, len(devices)))
        self.mesh = jax.sharding.Mesh(np.asarray(devices), (axis,))
        self._profiles: Dict = {}
        if registry is None:
            from ..obs import default_registry
            registry = default_registry()
        from ..obs import adapters as obs_adapters
        m = obs_adapters.ensure_comm_metrics(registry, 0, self._d,
                                             backend="mesh")
        self._m_sent = m["lgbm_comm_bytes_sent_total"]
        self._m_recv = m["lgbm_comm_bytes_received_total"]
        self._m_rounds = m["lgbm_comm_allgather_total"]

    @property
    def rank(self) -> int:
        return 0

    @property
    def world(self) -> int:
        return self._d

    def allreduce(self, value, op: str = "sum"):
        return value          # host values are already global

    def allgather(self, payload) -> List:
        return [payload] * self._d

    def fence(self) -> int:
        return 0

    def shard_map(self, fn, in_specs, out_specs):
        return shard_mapped(fn, self.mesh, in_specs, out_specs)

    def bind(self, key, fn):
        """Wrap a jitted shard_mapped callable: the first call runs under
        ``capture_traced`` (tracing happens inside it, so the collective
        profile lands here exactly once per compilation); every call
        re-emits that profile as counters + a comm/mesh_psum span."""
        def wrapped(*args):
            prof = self._profiles.get(key)
            if prof is None:
                prof = {}
                with capture_traced(prof):
                    out = fn(*args)
                self._profiles[key] = prof
            else:
                out = fn(*args)
            self._emit(prof)
            return out
        return wrapped

    def _emit(self, prof: Dict[str, Tuple[int, int]]) -> None:
        if not prof:
            return
        ops = sum(c for c, _ in prof.values())
        nbytes = sum(b for _, b in prof.values())
        # logical payload bytes: what one shard contributes to (and
        # receives from) the reduction — the mesh moves them over ICI,
        # never through the host
        self._m_sent.inc(nbytes)
        self._m_recv.inc(nbytes)
        self._m_rounds.inc(ops)
        from ..obs import tracing
        if tracing.get_tracer().enabled:
            tracing.complete(
                "comm/mesh_psum", 0.0, cat="comm", nbytes=nbytes, ops=ops,
                world=self._d,
                **{k: dict(count=c, bytes=b) for k, (c, b) in prof.items()})


class SocketAxis:
    """Traced-collective handle for the socket backend.

    Grow-loop collectives become ORDERED host callbacks into the wrapped
    comm, so the same grow program that psums over a mesh axis string
    rendezvouses over TCP when handed this instead.  Every rank runs the
    identical program, so callbacks fire in the same order on every rank
    (the symmetry the tpulint ``collectives`` family enforces); each op
    carries a sequence tag and the combine verifies all ranks sent the
    same one, so a desync fails loudly instead of summing mismatched
    payloads.

    Exceptions inside an XLA host callback cannot propagate cleanly, so
    wire failures (CommFailure / WorldChangedError — the elastic fence)
    are parked on ``failure`` and re-raised by ``check_failure`` once the
    program returns; the payload degrades to zeros in the meantime.
    """

    def __init__(self, collective: "SocketCollective"):
        self._coll = collective
        self.rank = collective.rank
        self.world = collective.world
        self._seq = 0
        self.failure: Optional[BaseException] = None

    # static-arg hashability: jitted growers close over this handle
    def __hash__(self):
        return id(self)

    def __eq__(self, other):
        return self is other

    def _next_tag(self, kind: str) -> str:
        self._seq += 1
        return "%s:%d" % (kind, self._seq)

    def _call(self, fn, x, out_shape):
        from jax.experimental import io_callback
        return io_callback(fn, out_shape, x, ordered=True)

    def _host(self, kind: str, op: str, arr: np.ndarray,
              stack: bool) -> np.ndarray:
        tag = self._next_tag(kind)
        try:
            parts = self._coll.exchange_arrays(tag, np.asarray(arr))
            if stack:
                return np.stack(parts)
            out = parts[0].copy()
            for p in parts[1:]:
                out = np.maximum(out, p) if op == "max" else out + p
            return out.astype(arr.dtype, copy=False)
        except BaseException as exc:  # noqa: BLE001 — park, don't crash XLA
            if self.failure is None:
                self.failure = exc
            shape = ((self.world,) + arr.shape) if stack else arr.shape
            return np.zeros(shape, arr.dtype)

    def allreduce(self, x, op: str):
        x = jnp.asarray(x)
        out = jax.ShapeDtypeStruct(x.shape, x.dtype)
        return self._call(partial(self._host, "allreduce", op, stack=False),
                          x, out)

    def gather(self, x):
        x = jnp.asarray(x)
        out = jax.ShapeDtypeStruct((self.world,) + x.shape, x.dtype)
        return self._call(partial(self._host, "gather", "sum", stack=True),
                          x, out)

    def scatter_reduce(self, x, **kwargs):
        total = self.allreduce(x, "sum")
        per = total.shape[0] // self.world
        return jax.lax.dynamic_slice_in_dim(total, self.rank * per, per)

    def check_failure(self) -> None:
        if self.failure is not None:
            failure, self.failure = self.failure, None
            raise failure


class SocketCollective(Collective):
    """The SocketComm/ElasticComm wire behind the Collective interface.

    Delegation preserves the wrapped comm's whole resilience surface:
    ``_with_retry`` retry budgets, heartbeat liveness, poison frames and
    generation fencing all fire exactly as they do for the find-bin and
    elastic-sync allgathers that already ride this wire."""

    backend = "socket"

    def __init__(self, comm):
        self.comm = comm
        self._axis: Optional[SocketAxis] = None
        self._row_layout: Optional[Tuple[int, int]] = None

    @property
    def rank(self) -> int:
        return self.comm.rank

    @property
    def world(self) -> int:
        return self.comm.world

    def axis(self) -> SocketAxis:
        """The traced-collective handle for this comm (one per booster
        generation: a re-formed world gets a fresh axis + sequence)."""
        if self._axis is None:
            self._axis = SocketAxis(self)
        return self._axis

    # -- host payloads --------------------------------------------------
    def allgather(self, payload) -> List:
        return [p.get("v") if isinstance(p, dict) else None
                for p in self.comm.allgather({"v": payload})]

    def allreduce(self, value, op: str = "sum"):
        arr = np.asarray(value)
        parts = self.exchange_arrays("host:%s" % op, arr)
        out = parts[0].copy()
        for p in parts[1:]:
            out = np.maximum(out, p) if op == "max" else out + p
        return out.astype(arr.dtype, copy=False)

    def exchange_arrays(self, tag: str, arr: np.ndarray) -> List[np.ndarray]:
        """Allgather one ndarray (rank order), verifying every rank is in
        the same collective (same tag) — the wire-level symmetry check."""
        payload = {"tag": tag, "dtype": str(arr.dtype),
                   "shape": list(arr.shape), "v": arr.tolist()}
        replies = self.comm.allgather(payload)
        parts: List[np.ndarray] = []
        for r, p in enumerate(replies):
            if p is None or p.get("tag") != tag:
                raise RuntimeError(
                    "collective desync: rank %d sent %r during %r"
                    % (r, None if p is None else p.get("tag"), tag))
            parts.append(np.asarray(p["v"], _np_dtype(p["dtype"]))
                         .reshape(p["shape"]))
        return parts

    def row_layout(self, local_rows: int) -> Tuple[int, int]:
        """(global_rows, this rank's row offset) for the contiguous
        pre-partitioned shard layout — agreed once per booster via one
        tiny allgather (the quantized global-noise slice needs it)."""
        if self._row_layout is None:
            counts = [int(c[0]) for c in self.exchange_arrays(
                "row_layout", np.asarray([local_rows], np.int64))]
            start = int(sum(counts[:self.rank]))
            self._row_layout = (int(sum(counts)), start)
        return self._row_layout

    # -- membership / fencing -------------------------------------------
    def fence(self) -> int:
        self.exchange_arrays("fence", np.asarray([self.generation()],
                                                 np.int64))
        return self.generation()

    def generation(self) -> int:
        return int(getattr(self.comm, "generation", 0))

    def world_changed(self):
        wc = getattr(self.comm, "world_changed", None)
        return wc() if callable(wc) else None

    def fenced_ranks(self) -> Tuple[int, ...]:
        fr = getattr(self.comm, "fenced_ranks", None)
        return tuple(fr()) if callable(fr) else ()

    def close(self) -> None:
        self.comm.close()


# --------------------------------------------------------------------- #
# Backend selection
# --------------------------------------------------------------------- #

_process_comm = None
_process_comm_lock = threading.Lock()


def set_process_comm(comm) -> None:
    """Attach (or clear, with None) this process's cross-host comm so
    ``make_collective`` can wrap it.  The elastic supervisor attaches its
    generation's ElasticComm here before building each booster."""
    global _process_comm
    with _process_comm_lock:
        _process_comm = comm


def get_process_comm():
    with _process_comm_lock:
        return _process_comm


def _mesh_devices_available() -> int:
    # the mesh_unavailable chaos drill (tools/chaos_run.py) forces the
    # mesh path down to exercise the socket fallback
    chaos = os.environ.get("LGBM_TPU_CHAOS", "")
    if chaos.split(":")[0] == "mesh_unavailable":
        return 0
    return jax.device_count()


def resolve_backend(config) -> str:
    """tpu_comm_backend -> concrete backend
    ('hybrid'|'mesh'|'socket'|'none'), given what is actually available
    in this process."""
    want = getattr(config, "tpu_comm_backend", "auto")
    comm = get_process_comm()
    have_socket = comm is not None and comm.world > 1
    have_mesh = _mesh_devices_available() > 1
    if want == "hybrid":
        if have_socket and have_mesh:
            return "hybrid"
        if have_socket:
            log.warning("tpu_comm_backend=hybrid but fewer than two local "
                        "devices are visible; falling back to the socket "
                        "backend")
            return "socket"
        if have_mesh:
            log.warning("tpu_comm_backend=hybrid but no cross-host comm is "
                        "attached to this process; using the mesh backend")
            return "mesh"
        return "none"
    if want == "socket":
        if have_socket:
            return "socket"
        log.warning("tpu_comm_backend=socket but no cross-host comm is "
                    "attached to this process; %s",
                    "using the mesh backend" if have_mesh
                    else "using the serial learner")
        return "mesh" if have_mesh else "none"
    if want == "mesh":
        if have_mesh:
            return "mesh"
        if have_socket:
            log.warning("tpu_comm_backend=mesh but fewer than two local "
                        "devices are visible; falling back to the socket "
                        "backend")
            return "socket"
        return "none"
    # auto: in-process mesh when the local devices allow it; a
    # multi-process world keeps its existing per-rank behavior unless
    # the socket backend is requested explicitly (docs/Distributed.md)
    return "mesh" if have_mesh else "none"


# one comm_backend recorder event per backend RESOLUTION, not per
# train() call: re-training on an unchanged topology says nothing new,
# while an actual change (fallback, re-formation shrinking the world)
# must stay observable for the chaos drills to assert on
_comm_event_lock = threading.Lock()
_last_comm_event: Optional[Tuple[str, str]] = None


def _reset_comm_backend_event() -> None:
    """Test hook: forget the last emitted (requested, topology) key."""
    global _last_comm_event
    with _comm_event_lock:
        _last_comm_event = None


def make_collective(config, num_machines: Optional[int] = None,
                    devices=None) -> Optional[Collective]:
    """Resolve tpu_comm_backend and build the backend, emitting a
    ``comm_backend`` recorder event tagged requested-vs-resolved on
    every topology change (the chaos drill's observable).  Returns None
    when no collective backend is available (serial)."""
    requested = getattr(config, "tpu_comm_backend", "auto")
    backend = resolve_backend(config)
    coll: Optional[Collective] = None
    if backend == "hybrid":
        from .hybrid import HybridCollective, resolve_local_devices
        local = resolve_local_devices(config, _mesh_devices_available())
        if local > 1:
            coll = HybridCollective(get_process_comm(), local,
                                    devices=devices)
        else:
            backend = "socket"
            coll = SocketCollective(get_process_comm())
    elif backend == "socket":
        coll = SocketCollective(get_process_comm())
    elif backend == "mesh":
        if num_machines is None:
            from .learners import resolve_num_machines
            num_machines = resolve_num_machines(config)
        if num_machines > 1:
            coll = MeshCollective(num_machines, devices=devices)
        else:
            backend = "none"
    if coll is None:
        topology = "none"
    elif backend == "hybrid":
        topology = "hybrid[%dx%d]" % (coll.world, coll.local_world)
    else:
        topology = "%s[%d]" % (backend, coll.world)
    global _last_comm_event
    with _comm_event_lock:
        emit = (requested, topology) != _last_comm_event
        if emit:
            _last_comm_event = (requested, topology)
    if emit:
        from ..obs.recorder import comm_backend_event
        comm_backend_event(config, backend, requested=requested,
                           topology=topology,
                           world=coll.world if coll is not None else 1)
    return coll
