"""Taskpool→XLA lowering: compile a regular PTG dataflow to ONE jitted program.

The reference executes every task through the dynamic scheduler; on TPU that
host-dispatch loop caps MFU long before the MXU does.  The TPU-first answer
(SURVEY §7 "design stance") is a *compilation step*: a PTG taskpool whose
execution space and guards are regular is lowered — through the same
chore/incarnation contract the dynamic path uses (``parsec_internal.h:396-402``)
— into a single XLA program over stacked tile stores.  "Fused" is thereby a
real incarnation of the taskpool, not a bypass: the input of this module is
the *task graph itself* (classes, flows, guarded deps, kernel names), and the
output is an executable the driver benches.

Pipeline:

1. **Analysis** — enumerate each class's execution space, evaluate guards
   concretely, and build the full task DAG (the same information
   ``iterate_successors`` walks at runtime, SURVEY §3.3).
2. **Store allocation** — every referenced data collection becomes one
   stacked device array ``[n_tiles, tile_h, tile_w]`` (tiles must be uniform;
   ragged edges fall back to the dynamic runtime).
3. **Chain-collapse pass** — the flagship optimization: a task class whose
   RW flow forms a linear accumulation chain over one parameter, fed by two
   READ flows with *factorized* keys (one ignores the chain's co-parameters
   of the other), and whose kernel incarnation is declared **bilinear**
   (``out = acc + lhs·rhs`` on tiles) collapses into one batched contraction
   over the tile stores — the k-chain of GEMM(m,n,k) becomes a single
   ``einsum('mkab,knbc->mnac')`` that XLA tiles onto the MXU at full size.
4. **Wavefront-batch pass** — the general MXU-saturation pass (the compiled
   analog of the device module's fused batching, and of the reference GPU
   hook keeping a stream full across a whole panel, ``jdf2c.c:6566``,
   ``device_gpu.c:2522-2531``): every flow value is resolved to a *store
   row* (tile dataflow is tile versioning), tasks are grouped per
   (topological wavefront, class, source signature), and each group becomes
   ONE ``jax.vmap``-batched kernel call over rows gathered from the stores —
   O(wavefronts·classes) program size instead of O(tasks), and the trailing
   update of a whole Cholesky panel lands on the MXU as one batched matmul.
5. **Unrolled dataflow fallback** — any other regular DAG is traced task by
   task in topological order inside one jit; XLA fuses from there.

Kernels participate by registering a *traceable incarnation* — a pure
jax-traceable function of the flow values — next to their dynamic-path body
(``register_traceable``; the ``dyld=`` name is shared, mirroring
``find_incarnation``'s per-device dlsym, ``device_gpu.c:201``).
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Any, Callable

import numpy as np

from ..core.params import params as _params
from ..data.data import ACCESS_RW, ACCESS_WRITE
from ..device.compile_cache import ensure_compile_cache

__all__ = ["LoweringError", "register_traceable", "find_traceable",
           "lower_taskpool", "LoweredTaskpool", "lowering_cache",
           "lower_regions", "RegionLoweredTaskpool", "LoweredRegion",
           "warm_cache", "structural_fingerprint"]

_params.register(
    "lowering_scan_min", 4,
    "fold this many (or more) consecutive identical wavefronts into one "
    "lax.scan body — O(1) trace/compile cost for uniform sweeps; runs "
    "shorter than this unroll (cross-level fusion may win there)")
_params.register(
    "lowering_cache", True,
    "memoize jitted lowered executables process-wide, keyed by the "
    "lowering's structural signature (task classes, store rows, kernels, "
    "mesh) — a re-lowered identical taskpool skips trace + compile")
_params.register(
    "lowering_region_max_tasks", 256,
    "member cap per megakernel region (analysis.regions): regions are "
    "convex wavefront-level bands of the verified task graph, one jitted "
    "XLA program each — smaller regions mean cheaper per-region compiles "
    "under lowering_compile_budget_s, more runtime boundaries; 0 lowers "
    "each weakly-connected component whole")
# the autotuner's declared domain (docs/TUNING.md): region caps move in
# powers of two between tiny (cheap compiles, many boundaries) and 1024
_params.declare_knob("lowering_region_max_tasks", lo=16, hi=1024,
                     scale="log2")
_params.register(
    "lowering_compile_budget_s", 0.0,
    "wall-clock budget for staged region compilation (smallest region "
    "first): once the budget is spent, remaining regions fall back to "
    "the eager (uncompiled, op-by-op) path instead of risking a stage "
    "deadline death mid-XLA-compile (BENCH_r04/r05, rc 124); cache hits "
    "are always free; 0.0 = unbudgeted")


class LoweringError(RuntimeError):
    """Raised when a taskpool cannot be lowered (irregular structure,
    non-traceable bodies, ragged tiles...).  Callers fall back to the
    dynamic runtime — lowering is an optimization, never a requirement."""


# ---------------------------------------------------------------------------
# traceable-kernel registry (the compiled-incarnation side of ``dyld=``)
# ---------------------------------------------------------------------------

class Traceable:
    """A jax-traceable incarnation of a task body.

    ``apply(*flow_values) -> value | tuple`` receives the task's non-CTL flow
    values in flow order and returns the new value(s) of its writable
    (RW/WRITE) flows, in flow order.

    ``bilinear=True`` declares tile-matmul semantics ``acc' = acc + lhs @
    rhs`` (fp32 accumulate) — lhs/rhs being the class's two READ flows *in
    declaration order* and acc its RW flow — enabling the chain-collapse
    pass; ``chain_combine(lhs_stack, rhs_stack, acc0)`` may override the
    default batched-einsum emission.
    """

    __slots__ = ("apply", "bilinear", "chain_combine")

    def __init__(self, apply: Callable, bilinear: bool = False,
                 chain_combine: Callable | None = None) -> None:
        self.apply = apply
        self.bilinear = bilinear
        self.chain_combine = chain_combine or (
            _default_bilinear_chain if bilinear else None)


def _default_bilinear_chain(lhs: Any, rhs: Any, acc0: Any) -> Any:
    """Collapse an accumulation chain: ``acc0[m,n] + sum_k lhs[m,k]·rhs[k,n]``
    over tile stacks — one dot_general contracting (k, tile-k), which XLA
    lays out as a full-size MXU matmul.

    Honors the ``gemm_precision`` MCA param exactly like the dynamic-path
    kernel (``ops/gemm.py``): ``highest`` forces full-precision multiplies
    on TPU, where the default would run f32 tiles through bf16 MXU passes
    and diverge from the dynamic runtime's CPU-f32 results."""
    import jax
    import jax.numpy as jnp

    from ..core.params import params as _cparams
    try:
        prec = (jax.lax.Precision.HIGHEST
                if _cparams.get("gemm_precision") == "highest" else None)
    except KeyError:
        prec = None
    acc = jnp.einsum("mkab,knbc->mnac", lhs, rhs,
                     preferred_element_type=jnp.float32, precision=prec)
    return (acc0.astype(jnp.float32) + acc).astype(acc0.dtype)


_lock = threading.Lock()
_traceables: dict[str, Traceable] = {}


def register_traceable(name: str, apply: Callable, *, bilinear: bool = False,
                       chain_combine: Callable | None = None) -> Traceable:
    t = Traceable(apply, bilinear=bilinear, chain_combine=chain_combine)
    with _lock:
        _traceables[name] = t
    return t


def find_traceable(name: str) -> Traceable | None:
    with _lock:
        return _traceables.get(name)


# ---------------------------------------------------------------------------
# persistent lowering/compile cache
# ---------------------------------------------------------------------------

def _freeze(o: Any):
    """Hashable deep-freeze of a pass's emission payload.  Small arrays
    freeze by value (shape + dtype + bytes); large ones by a blake2b
    digest, so a task-sized plan does not pin megabytes of copied index
    bytes in every signature; callables freeze by IDENTITY — the key keeps
    them alive, and two distinct closures can never false-hit."""
    if isinstance(o, np.ndarray):
        b = o.tobytes()
        if len(b) > 4096:
            import hashlib
            b = hashlib.blake2b(b, digest_size=20).digest()
        return ("nd", o.shape, o.dtype.str, b)
    if isinstance(o, dict):
        return tuple(sorted((k, _freeze(v)) for k, v in o.items()))
    if isinstance(o, (list, tuple)):
        return tuple(_freeze(v) for v in o)
    return o


class LoweringCache:
    """Process-global memo of jitted lowered executables.

    A lowering pass emits a *structural signature* alongside its step
    function: the exact closure payload the traced program depends on
    (store names/rows, kernel callables by identity, gather/scatter index
    arrays by value).  Equal signature ⇒ byte-identical traced program, so
    a re-lowered structurally identical taskpool reuses the already-traced,
    already-compiled executable instead of re-paying ``*_compile_s`` —
    repeat bench stages and repeat served submissions hit here.
    Bounded FIFO (oldest evicted) so many distinct lowerings cannot grow
    it without bound."""

    MAX_ENTRIES = 128

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._jitted: dict = {}
        self.hits = 0
        self.misses = 0

    def peek(self, key) -> Any:
        """Probe without building (no hit/miss accounting): the compile-
        budget layer asks "is this region already paid for?" before
        deciding whether the budget can afford a fresh compile."""
        if key is None:
            return None
        with self._lock:
            return self._jitted.get(key)

    def get_or_build(self, key, build: Callable[[], Any]):
        if key is None:
            return build()
        with self._lock:
            f = self._jitted.get(key)
            if f is not None:
                self.hits += 1
                return f
        f = build()     # outside the lock: a trace/compile can be seconds
        with self._lock:
            # a concurrent builder may have won the race: keep and return
            # ITS entry, so identity sharing holds across racing threads
            won = self._jitted.setdefault(key, f)
            if won is f:
                self.misses += 1
            else:
                self.hits += 1
            while len(self._jitted) > self.MAX_ENTRIES:
                self._jitted.pop(next(iter(self._jitted)))
        return won

    def clear(self) -> None:
        with self._lock:
            self._jitted.clear()
            self.hits = 0
            self.misses = 0


lowering_cache = LoweringCache()


def _backend_signature() -> tuple:
    """The (jax version, backend, device kind) triple folded into every
    executable cache key: an in-process cache consulted after a backend
    flip (JAX_PLATFORMS override mid-process, tests forcing cpu) and a
    compile-cache directory shared across CPU/TPU processes must never
    serve an executable compiled for the other backend."""
    import jax
    try:
        kind = getattr(jax.devices()[0], "device_kind", "")
    except Exception:
        kind = ""
    return (jax.__version__, jax.default_backend(), kind)


def structural_fingerprint(obj) -> dict:
    """Cross-process-stable structural summary of a taskpool — the tune
    subsystem's signature seam (``parsec_tpu/tune/signature.py``,
    docs/TUNING.md).

    The in-process lowering signatures (:func:`_freeze`) key callables
    by IDENTITY, which is exactly right for an executable cache and
    exactly wrong for a persistent tuning DB: two processes lowering the
    same program would never agree.  This export keeps only the stable
    axes those signatures discriminate on — task classes (name, task
    count, kernel NAME, flow names), the wavefront shape (level count,
    widest level), and, when handed an already-lowered pool, the chosen
    mode and per-store row geometry — as a plain JSON-able dict.
    Accepts a Taskpool or a :class:`LoweredTaskpool`."""
    low = obj if isinstance(obj, LoweredTaskpool) else None
    tp = low.taskpool if low is not None else obj
    infos = _analyze(tp)
    classes = []
    total = 0
    for cname in sorted(infos):
        ci = infos[cname]
        k = ci.kernel
        kname = ""
        if k is not None:
            kname = (getattr(k, "name", None)
                     or getattr(getattr(k, "fn", None), "__name__", "")
                     or "")
        total += len(ci.tasks)
        classes.append([cname, len(ci.tasks), kname,
                        sorted(f.name for f in ci.data_flows),
                        sorted(f.name for f in ci.writable_flows)])
    fp: dict = {"classes": classes, "ntasks": total}
    try:
        _order, levels = _task_graph(tp, infos)
        if levels:
            widths: dict[int, int] = {}
            for lv in levels.values():
                widths[lv] = widths.get(lv, 0) + 1
            fp["wavefront"] = [1 + max(levels.values()),
                               max(widths.values())]
    except LoweringError:
        pass        # irregular graph: the class table still discriminates
    if low is not None:
        fp["mode"] = low.mode
        fp["stores"] = {name: int(low._stores.nrows.get(name, 0))
                        for name in sorted(low._stores.dcs)}
    return fp


# ---------------------------------------------------------------------------
# analysis
# ---------------------------------------------------------------------------

class _ClassInfo:
    __slots__ = ("tc", "tasks", "kernel", "data_flows", "writable_flows")

    def __init__(self, tc, tasks, kernel):
        self.tc = tc
        self.tasks = tasks              # list[dict] locals, enumeration order
        self.kernel = kernel            # Traceable | None
        self.data_flows = [f for f in tc.flows if not f.is_ctl]
        self.writable_flows = [f for f in self.data_flows
                               if f.access in (ACCESS_RW, ACCESS_WRITE)]


def _class_kernel(tc, local: dict | None = None) -> Traceable | None:
    for chore in tc.chores:
        if chore.dyld is not None:
            t = (local or {}).get(chore.dyld) or find_traceable(chore.dyld)
            if t is not None:
                return t
    return None


def _analyze(tp) -> dict[str, _ClassInfo]:
    # taskpools may carry build-scoped traceables (per-instance constants
    # like stencil weights) without touching the process-global registry
    local = getattr(tp, "local_traceables", None)
    infos: dict[str, _ClassInfo] = {}
    for tc in tp.task_classes:
        tcb = tp._tc_builders[tc.name]
        tasks = list(tcb._enumerate_space())
        kernel = _class_kernel(tc, local)
        if kernel is None and any(not f.is_ctl for f in tc.flows):
            raise LoweringError(
                f"task class {tc.name} has data flows but no traceable "
                f"kernel incarnation (register_traceable under its dyld name)")
        if getattr(tc, "stage_in_hook", None) is not None \
                or getattr(tc, "stage_out_hook", None) is not None:
            raise LoweringError(
                f"task class {tc.name}: custom stage hooks own data "
                f"placement — they run on the dynamic device path only")
        for f in tc.flows:
            for d in (*f.deps_in, *f.deps_out):
                if d.dtt is not None:
                    raise LoweringError(
                        f"{tc.name}.{f.name}: typed dep edges "
                        f"([type=...]) reshape on the dynamic path")
            for d in f.deps_in:
                if d.target_class is None and d.data_ref is None \
                        and not d.null:
                    # NEW arrow: the lowering allocates the scratch — a
                    # zeros tile of the declared type, matching the
                    # dynamic path's prepare_input allocation — so the
                    # type must be statically known
                    if d.dtt is None and f.dtt is None:
                        raise LoweringError(
                            f"{tc.name}.{f.name}: NEW input without a "
                            f"declared tile type (pass dtt=)")
        infos[tc.name] = _ClassInfo(tc, tasks, kernel)
    return infos


def _collection_keys(dc) -> list[tuple]:
    from ..data_dist.collection import enumerate_keys
    try:
        return enumerate_keys(dc)
    except TypeError as e:
        raise LoweringError(str(e))


def _norm_key(key) -> tuple:
    return key if isinstance(key, tuple) else (key,)


class _Stores:
    """One device array per referenced collection.

    Layout per collection is chosen by the lowering passes: ``stacked``
    (``[n_tiles, h, w]``, supports arbitrary gathers) or ``dense`` (the
    whole matrix ``[lm, ln]``, chosen when a pass proves its accesses form
    the identity tile grid — the fused program then reads the operand in
    its natural layout with zero gather/relayout cost).

    With ``nranks`` set (multi-rank lowering), stacked stores are laid out
    **rank-major**: the tiles rank *r* owns (``dc.rank_of``) occupy the
    contiguous row slab ``[r*cap, (r+1)*cap)``, zero-padded to the largest
    per-rank count — so sharding axis 0 over a ``ranks`` mesh axis places
    every tile exactly on its owning device, and cross-rank dep edges
    surface as XLA gathers that GSPMD lowers to collectives."""

    def __init__(self, nranks: int | None = None):
        self.dcs: dict[str, Any] = {}
        self.rows: dict[str, dict[tuple, int]] = {}
        self.written: set[str] = set()
        self.layout: dict[str, str] = {}
        self.nranks = nranks
        self.nrows: dict[str, int] = {}     # total rows incl. padding
        self.replicated: set[str] = set()   # nodes==1 collections
        self.shape: dict[str, tuple] = {}   # uniform tile shape per store
        self.dtype: dict[str, Any] = {}
        self.open: set[str] = set()         # lazily-extended key spaces
        self.scratch: set[str] = set()      # synthetic NEW-flow stores

    def _ensure(self, dc) -> None:
        name = dc.name
        if name in self.dcs:
            return
        try:
            keys = _collection_keys(dc)
        except LoweringError:
            keys = []                   # non-enumerable: open key space
        # an undeclared dict collection is open even when some keys are
        # already materialized (a seeded token chain, ISSUE 9): the pool
        # may write fresh keys its has_key oracle vouches for.  Multi-
        # rank lowering keeps the closed snapshot — open spaces have no
        # enumerable ownership to shard by.
        open_space = (not keys or bool(getattr(dc, "open_key_space",
                                               False)))
        if open_space and self.nranks is None:
            # open store (paged-KV block tables, writeback-only dict
            # collections): rows beyond the pre-registered ones
            # materialize on first reference through the collection's
            # own has_key/data_of oracles
            self.dcs[name] = dc
            self.rows[name] = {_norm_key(k): i for i, k in enumerate(keys)}
            self.nrows[name] = len(keys)
            self.layout[name] = "stacked"
            self.open.add(name)
            if keys:
                first = np.asarray(
                    dc.data_of(*keys[0]).newest_copy().value)
                self.shape[name] = tuple(first.shape)
                self.dtype[name] = first.dtype
            return
        if not keys:
            raise LoweringError(
                f"collection {name}: open key spaces do not lower "
                f"multi-rank (no enumerable ownership)")
        shapes = {dc.tile_shape(*k) if hasattr(dc, "tile_shape")
                  else np.asarray(dc.data_of(*k).newest_copy().value).shape
                  for k in keys}
        if len(shapes) != 1:
            raise LoweringError(
                f"collection {name} has ragged tiles {shapes}; "
                f"lowering needs uniform tile shapes")
        self.dcs[name] = dc
        if self.nranks is not None and getattr(dc, "nodes", 1) > 1:
            if dc.nodes != self.nranks:
                raise LoweringError(
                    f"collection {name} is distributed over {dc.nodes} "
                    f"ranks but the mesh has {self.nranks}")
            by_rank: dict[int, list[tuple]] = {}
            for k in keys:
                by_rank.setdefault(dc.rank_of(*k), []).append(k)
            cap = max(len(v) for v in by_rank.values())
            rows: dict[tuple, int] = {}
            for r in range(self.nranks):
                for i, k in enumerate(by_rank.get(r, ())):
                    rows[k] = r * cap + i
            self.rows[name] = rows
            self.nrows[name] = self.nranks * cap
        else:
            self.rows[name] = {k: i for i, k in enumerate(keys)}
            self.nrows[name] = len(keys)
            if self.nranks is not None:
                self.replicated.add(name)
        self.layout[name] = "stacked"
        if hasattr(dc, "tile_shape") and getattr(dc, "dtype", None) \
                is not None:
            # declared geometry: planning (and the AOT warm path, whose
            # contract is "no tile materialized") stays allocation-free
            self.shape[name] = tuple(next(iter(shapes)))
            self.dtype[name] = np.dtype(dc.dtype)
        else:
            first = np.asarray(dc.data_of(*keys[0]).newest_copy().value)
            self.shape[name] = tuple(first.shape)
            self.dtype[name] = first.dtype

    def row(self, dc, key: tuple) -> int:
        self._ensure(dc)
        name = dc.name
        r = self.rows[name].get(key)
        if r is not None:
            return r
        # lazy extension: legal only when the collection itself vouches
        # for the key (open dict stores answer has_key=True; a tiled
        # grid's out-of-bounds key stays a hard error)
        if name in self.open and getattr(dc, "has_key",
                                         lambda *k: False)(*key):
            val = np.asarray(dc.data_of(*key).newest_copy().value)
            shape = self.shape.setdefault(name, tuple(val.shape))
            self.dtype.setdefault(name, val.dtype)
            if tuple(val.shape) != shape:
                raise LoweringError(
                    f"collection {name}: ragged tiles "
                    f"({tuple(val.shape)} vs {shape}); lowering needs "
                    f"uniform tile shapes")
            r = self.nrows[name]
            self.rows[name][key] = r
            self.nrows[name] = r + 1
            return r
        raise LoweringError(f"{name}: key {key} outside the store")

    def scratch_row(self, cname: str, fname: str, key: tuple,
                    shape: tuple, dtype: Any) -> tuple[str, int]:
        """A row in the synthetic zero-initialized store backing a NEW
        arrow (the compiled analog of ``scratch_copy``): RW flows whose
        value never lands in a collection still need a store-resident
        home so successors can gather it."""
        name = f"_scratch_{cname}_{fname}"
        if name not in self.rows:
            self.rows[name] = {}
            self.nrows[name] = 0
            self.layout[name] = "scratch"
            self.shape[name] = tuple(shape)
            self.dtype[name] = np.dtype(dtype)
            self.scratch.add(name)
        r = self.rows[name].get(key)
        if r is None:
            r = self.nrows[name]
            self.rows[name][key] = r
            self.nrows[name] = r + 1
        return name, r

    def is_dense_grid(self, dc, I: np.ndarray) -> bool:
        """Whether index grid ``I`` is exactly the identity tile grid of the
        whole collection: ``I[i, j] == row of tile (i, j)``, every tile
        covered.  Pure check; commit with ``set_dense``."""
        name = dc.name
        if self.nranks is not None:
            return False   # dense re-layout would discard tile ownership
        if not (hasattr(dc, "mt") and hasattr(dc, "nt")):
            return False
        if I.shape != (dc.mt, dc.nt):
            return False
        if len(self.rows[name]) != dc.mt * dc.nt:
            return False
        expect = np.array([[self.rows[name][(m, n)] for n in range(dc.nt)]
                           for m in range(dc.mt)], I.dtype)
        return bool(np.array_equal(I, expect))

    def set_dense(self, dc) -> None:
        self.layout[dc.name] = "dense"

    def materialize(self) -> dict[str, Any]:
        """Gather tiles into host arrays (device placement is the caller's
        business — jit will device_put on first call).  Rank-major stores
        zero-fill their padding rows; scratch stores materialize as zeros
        (the NEW-arrow allocation policy, ``data.scratch_copy``)."""
        out = {}
        for name, dc in self.dcs.items():
            if self.layout[name] == "dense":
                out[name] = dc.to_dense()
                continue
            rows = self.rows[name]
            if not rows:
                continue            # ensured but never referenced
            first = np.asarray(
                dc.data_of(*next(iter(rows))).newest_copy().value)
            arr = np.zeros((self.nrows[name],) + first.shape, first.dtype)
            for k, i in rows.items():
                arr[i] = np.asarray(dc.data_of(*k).newest_copy().value)
            out[name] = arr
        for name in self.scratch:
            out[name] = np.zeros((self.nrows[name],) + self.shape[name],
                                 self.dtype[name])
        return out

    def avals(self) -> dict[str, Any]:
        """Abstract shapes/dtypes of :meth:`materialize`'s output — what
        AOT cache warming traces against so compiles happen WITHOUT
        materializing (or moving) a single tile."""
        import jax
        out = {}
        for name, dc in self.dcs.items():
            if not self.rows[name]:
                continue
            if self.layout[name] == "dense":
                out[name] = jax.ShapeDtypeStruct(
                    (dc.lm, dc.ln), np.dtype(dc.dtype))
            else:
                out[name] = jax.ShapeDtypeStruct(
                    (self.nrows[name],) + self.shape[name],
                    self.dtype[name])
        for name in self.scratch:
            out[name] = jax.ShapeDtypeStruct(
                (self.nrows[name],) + self.shape[name], self.dtype[name])
        return out

    def writeback(self, values: dict[str, Any]) -> None:
        for name in self.written:
            dc = self.dcs[name]
            arr = np.asarray(values[name])
            for key, i in self.rows[name].items():
                copy = dc.data_of(*key).newest_copy()
                # per-tile host copies: np.asarray over a jax array yields
                # read-only views, and task bodies mutate tiles in place
                if self.layout[name] == "dense":
                    m, n = key
                    copy.value = np.array(arr[m * dc.mb:(m + 1) * dc.mb,
                                              n * dc.nb:(n + 1) * dc.nb])
                else:
                    copy.value = np.array(arr[i])
                copy.version += 1


# ---------------------------------------------------------------------------
# pass 1: bilinear chain collapse
# ---------------------------------------------------------------------------

def _active_in_deps(flow, locals_):
    return [d for d in flow.deps_in if d.active(locals_)]


def _active_out_deps(flow, locals_):
    return [d for d in flow.deps_out if d.active(locals_)]


def _key_param_deps(tasks: list[dict], keys: list[tuple],
                    params: list[str]) -> set[str]:
    """Which params influence ``key`` — decided concretely: q matters iff two
    tasks differing only in q have different keys."""
    deps: set[str] = set()
    for q in params:
        rest = [p for p in params if p != q]
        seen: dict[tuple, Any] = {}
        for loc, key in zip(tasks, keys):
            r = tuple(loc[p] for p in rest)
            if r in seen and seen[r] != key:
                deps.add(q)
                break
            seen.setdefault(r, key)
    return deps


def _try_chain_collapse(tp, infos, stores: _Stores):
    """Detect ``ACC(p..., k)``: init-from-store at k=lo, accumulate lhs·rhs
    along k, write-to-store at k=hi — and emit one contraction."""
    if len(infos) != 1:
        return None
    (info,) = infos.values()
    tc, kernel, tasks = info.tc, info.kernel, info.tasks
    if kernel is None or not kernel.bilinear or not tasks:
        return None
    if len(info.data_flows) != 3 or len(info.writable_flows) != 1:
        return None
    acc = info.writable_flows[0]
    lhs, rhs = [f for f in info.data_flows if f is not acc]
    params = tc.params

    # -- identify the chain parameter from any interior pred edge ------------
    chain = None
    for loc in tasks:
        for d in _active_in_deps(acc, loc):
            if d.target_class == tc.name and d.target_flow == acc.name:
                pred = d.target_params(loc)
                if not isinstance(pred, dict):   # range arrow: not a chain
                    return None
                diff = [p for p in params if pred[p] != loc[p]]
                if len(diff) == 1 and loc[diff[0]] - pred[diff[0]] == 1:
                    chain = diff[0]
                break
        if chain:
            break
    if chain is None:
        return None

    kvals = sorted({loc[chain] for loc in tasks})
    if kvals != list(range(kvals[0], kvals[-1] + 1)):
        return None
    klo, khi = kvals[0], kvals[-1]

    # -- verify the chain structure concretely on every task -----------------
    lhs_keys, rhs_keys, acc_keys = [], [], []
    for loc in tasks:
        li = _active_in_deps(lhs, loc)
        ri = _active_in_deps(rhs, loc)
        ai = _active_in_deps(acc, loc)
        ao = _active_out_deps(acc, loc)
        if len(li) != 1 or li[0].data_ref is None:
            return None
        if len(ri) != 1 or ri[0].data_ref is None:
            return None
        if _active_out_deps(lhs, loc) or _active_out_deps(rhs, loc):
            return None
        if len(ai) != 1:
            return None
        if loc[chain] == klo:
            if ai[0].data_ref is None:
                return None
        else:
            d = ai[0]
            if (d.target_class != tc.name or d.target_flow != acc.name):
                return None
            pred = d.target_params(loc)
            if not isinstance(pred, dict):
                return None
            if any(pred[p] != (loc[p] - (p == chain)) for p in params):
                return None
        succ = [d for d in ao if d.target_class == tc.name
                and d.target_flow == acc.name]
        data_out = [d for d in ao if d.data_ref is not None]
        if loc[chain] < khi:
            if len(succ) != 1 or data_out:
                return None
            nxt = succ[0].target_params(loc)
            if not isinstance(nxt, dict):
                return None
            if any(nxt[p] != (loc[p] + (p == chain)) for p in params):
                return None
        else:
            if succ or len(data_out) != 1:
                return None
        lhs_keys.append((li[0].data_ref(loc)))
        rhs_keys.append((ri[0].data_ref(loc)))
        if loc[chain] == klo:
            acc_keys.append(ai[0].data_ref(loc))
        elif loc[chain] == khi:
            acc_keys.append(data_out[0].data_ref(loc))
        else:
            acc_keys.append(None)

    # -- factorization: lhs depends on (Pl, chain), rhs on (Pr, chain) -------
    lk = [_norm_key(k) for _, k in lhs_keys]
    rk = [_norm_key(k) for _, k in rhs_keys]
    free = [p for p in params if p != chain]
    ldeps = _key_param_deps(tasks, lk, params) - {chain}
    rdeps = _key_param_deps(tasks, rk, params) - {chain}
    if ldeps & rdeps or (ldeps | rdeps) != set(free):
        return None
    pl = sorted(ldeps, key=params.index)
    pr = sorted(rdeps, key=params.index)

    mvals = sorted({tuple(loc[p] for p in pl) for loc in tasks})
    nvals = sorted({tuple(loc[p] for p in pr) for loc in tasks})
    if len(tasks) != len(mvals) * len(nvals) * len(kvals):
        return None    # not a dense product space

    lhs_dc = lhs_keys[0][0]
    rhs_dc = rhs_keys[0][0]
    acc_dc = next(k for k in acc_keys if k is not None)[0]
    # every edge of a flow must read one single collection — a guarded
    # multi-collection input cannot collapse onto one store gather
    if any(dc is not lhs_dc for dc, _ in lhs_keys):
        return None
    if any(dc is not rhs_dc for dc, _ in rhs_keys):
        return None
    if any(k is not None and k[0] is not acc_dc for k in acc_keys):
        return None
    mi = {v: i for i, v in enumerate(mvals)}
    ni = {v: i for i, v in enumerate(nvals)}
    ki = {v: i for i, v in enumerate(kvals)}
    IA = np.zeros((len(mvals), len(kvals)), np.int32)
    IB = np.zeros((len(kvals), len(nvals)), np.int32)
    IC = np.full((len(mvals), len(nvals)), -1, np.int32)
    for loc, lkey, rkey, akey in zip(tasks, lk, rk, acc_keys):
        m = mi[tuple(loc[p] for p in pl)]
        n = ni[tuple(loc[p] for p in pr)]
        k = ki[loc[chain]]
        IA[m, k] = stores.row(lhs_dc, lkey)
        IB[k, n] = stores.row(rhs_dc, rkey)
        if akey is not None:
            row = stores.row(acc_dc, _norm_key(akey[1]))
            if IC[m, n] not in (-1, row):
                return None    # init and final writeback rows must agree
            IC[m, n] = row
    if (IC < 0).any():
        return None
    stores.written.add(acc_dc.name)

    combine = kernel.chain_combine
    an, bn, cn = lhs_dc.name, rhs_dc.name, acc_dc.name

    # -- layout selection: identity tile grids lower to dense operands -------
    # The contraction then reads each matrix in its natural [lm, ln] layout
    # and the emitted program is exactly ``C = tile_body(A, B, C)`` on dense
    # operands — zero gather/relayout traffic on the hot path.
    if (len({an, bn, cn}) == 3
            and stores.is_dense_grid(lhs_dc, IA)
            and stores.is_dense_grid(rhs_dc, IB)
            and stores.is_dense_grid(acc_dc, IC)):
        for dc in (lhs_dc, rhs_dc, acc_dc):
            stores.set_dense(dc)
        apply = kernel.apply
        # apply's contract is "flow values in declaration order" — respect
        # it even when the RW flow is not declared last
        arg_names = [{id(lhs): an, id(rhs): bn, id(acc): cn}[id(f)]
                     for f in info.data_flows]

        def step_fn(st: dict) -> dict:
            st = dict(st)
            st[cn] = apply(*(st[nm] for nm in arg_names))
            return st

        return step_fn, ("chain-dense", apply, tuple(arg_names), an, bn, cn)

    IC_flat = IC.reshape(-1)

    def step_fn(st: dict) -> dict:
        a = st[an][IA]                      # [M, K, ta, tk]
        b = st[bn][IB]                      # [K, N, tk, tb]
        c0 = st[cn][IC]                     # [M, N, ta, tb]
        c = combine(a, b, c0)
        st = dict(st)
        st[cn] = st[cn].at[IC_flat].set(c.reshape(-1, *c.shape[2:]))
        return st

    return step_fn, ("chain-gather", combine, an, bn, cn,
                     _freeze(IA), _freeze(IB), _freeze(IC))


# ---------------------------------------------------------------------------
# pass 2: wavefront batching (one vmapped kernel call per (level, class))
# ---------------------------------------------------------------------------

class _WFPlan:
    """The wavefront resolution of one taskpool: per-task gather/scatter
    plans against store rows, hazard-checked — the shared substrate of
    the whole-pool wavefront emission AND the per-region megakernel
    emission (which slices these plans into region-local programs)."""

    __slots__ = ("plans", "dirty_by_name", "levels")

    def __init__(self, plans, dirty_by_name, levels) -> None:
        # plans: [(node, level, cname, key, in_plan, out_plan)]
        self.plans = plans
        self.dirty_by_name = dirty_by_name
        self.levels = levels


def _wavefront_plan(tp, infos, stores: _Stores) -> _WFPlan:
    """Resolve every data-flow value to a store row and hazard-check the
    in-place row reuse (the shared analysis under the wavefront and
    region emissions).

    The key resolution step: *every data-flow value lives in a store row*.
    A task's input either names a collection tile directly (``data=``), a
    predecessor's flow value — which, recursively, is an updated *version*
    of some tile (tiled dataflow is tile versioning) — or a NEW arrow,
    backed by a zero-initialized synthetic scratch store.  Writable flows
    update their home row **in place**; successors gather from the same
    rows.  Versions are tracked statically and any interleaving where
    in-place reuse would clobber a still-needed version raises
    :class:`LoweringError` (→ unrolled pass / dynamic runtime).
    """
    order, levels = _task_graph(tp, infos)

    # ---- value/version resolution ------------------------------------------
    # value_of[(cname, key, flow_index)] = (store_name, row, version)
    #   version: ("init", L)    — row content as of the start of level L
    #            ("task", n, L) — written by node n at level L
    value_of: dict[tuple, tuple] = {}
    # writes[row] = [(level, node, is_scratch)] — is_scratch marks in-place
    # version storage (never a collection write in the source program)
    writes: dict[tuple[str, int], list[tuple[int, tuple, bool]]] = {}
    data_last: dict[tuple[str, int], int] = {}      # last collection write
    scratch_last: dict[tuple[str, int], int] = {}   # last in-place write
    reads: list[tuple[tuple[str, int], tuple, int]] = []

    plans = []
    for node in order:
        cname, i = node
        info = infos[cname]
        if not info.data_flows:
            continue                      # CTL-only class: shapes levels only
        tc, loc = info.tc, info.tasks[i]
        key = tc.make_key(loc)
        L = levels[node]
        writable_ids = {id(f) for f in info.writable_flows}
        # per flow: ("row", name, row) | ("none",) | ("new", shape, dtype)
        in_plan: list[tuple] = []
        in_vers: list[tuple | None] = []          # version read, per flow
        for f in info.data_flows:
            deps = _active_in_deps(f, loc)
            if len(deps) > 1:
                raise LoweringError(
                    f"{cname}{key} flow {f.name}: {len(deps)} active input "
                    f"deps — ambiguous source")
            if not deps or deps[0].null:
                in_plan.append(("none",))
                in_vers.append(None)
                continue
            d = deps[0]
            if d.data_ref is not None:
                dc, k = d.data_ref(loc)
                row = (dc.name, stores.row(dc, _norm_key(k)))
                ver = ("init", L)
            elif d.target_class is None:
                # NEW arrow: zeros of the declared type (scratch_copy's
                # policy).  A writable flow whose value never reaches a
                # collection still needs a store-resident home row so
                # successors can gather it — the synthetic scratch store;
                # otherwise the zeros synthesize inline in the program.
                dtt = d.dtt or f.dtt
                shape, dtype = tuple(dtt.shape), np.dtype(dtt.dtype)
                has_data_out = any(
                    dd.data_ref is not None
                    for dd in _active_out_deps(f, loc))
                if id(f) in writable_ids and not has_data_out:
                    row = stores.scratch_row(cname, f.name, key,
                                             shape, dtype)
                    ver = ("init", L)
                else:
                    in_plan.append(("new", shape, str(dtype)))
                    in_vers.append(None)
                    continue
            else:
                ptc = tp.task_class(d.target_class)
                pkey = ptc.make_key(d.target_params(loc))
                pfi = next(ff.flow_index for ff in ptc.flows
                           if ff.name == d.target_flow)
                try:
                    pname, prow, ver = value_of[(d.target_class, pkey, pfi)]
                except KeyError:
                    raise LoweringError(
                        f"{cname}{key} flow {f.name}: predecessor value "
                        f"{d.target_class}{pkey}.{d.target_flow} has no "
                        f"store-resident home")
                row = (pname, prow)
            reads.append((row, ver, L))
            in_plan.append(("row",) + row)
            in_vers.append(ver)
        out_plan = []               # (primary|None, extras, writable) per flow
        for fj, f in enumerate(info.data_flows):
            drows = []
            for d in _active_out_deps(f, loc):
                if d.data_ref is not None:
                    dc, k = d.data_ref(loc)
                    drows.append((dc.name, stores.row(dc, _norm_key(k))))
                    stores.written.add(dc.name)
            if id(f) in writable_ids:
                if drows:
                    primary, extras = drows[0], drows[1:]
                    data_last[primary] = max(data_last.get(primary, -1), L)
                    writes.setdefault(primary, []).append((L, node, False))
                else:
                    ip = in_plan[fj]
                    if ip[0] != "row":
                        raise LoweringError(
                            f"{cname}{key} flow {f.name}: writable flow with "
                            f"neither a collection target nor a "
                            f"store-resident input — no home row")
                    primary, extras = (ip[1], ip[2]), []
                    scratch_last[primary] = max(
                        scratch_last.get(primary, -1), L)
                    writes.setdefault(primary, []).append((L, node, True))
                value_of[(cname, key, f.flow_index)] = (
                    primary[0], primary[1], ("task", node, L))
                for w in extras:
                    writes.setdefault(w, []).append((L, node, False))
                    data_last[w] = max(data_last.get(w, -1), L)
                out_plan.append((primary, extras, True))
            else:
                ip = in_plan[fj]
                if ip[0] == "row":
                    # pass-through: successors read the same row/version
                    value_of[(cname, key, f.flow_index)] = (
                        ip[1], ip[2], in_vers[fj])
                elif drows and ip[0] != "new":
                    raise LoweringError(
                        f"{cname}{key} flow {f.name}: collection write from "
                        f"a flow with no input value")
                for w in drows:
                    writes.setdefault(w, []).append((L, node, False))
                    data_last[w] = max(data_last.get(w, -1), L)
                out_plan.append((None, drows, False))
        plans.append((node, L, cname, key, in_plan, out_plan))

    # ---- static hazard checks (violations → unrolled fallback) -------------
    for w, ws in writes.items():
        seen_levels = set()
        for lw, _, _ in ws:
            if lw in seen_levels:
                raise LoweringError(
                    f"store row {w}: two writers in one wavefront")
            seen_levels.add(lw)
    for row, ver, L in reads:
        if ver[0] == "task":
            # version must survive from its creation to this read: no other
            # write may land strictly between (snapshot semantics make
            # same-level writes safe)
            lo = ver[2]
            for lw, _, _ in writes.get(row, ()):
                if lo < lw < L:
                    raise LoweringError(
                        f"store row {row}: version created at level {lo} "
                        f"overwritten at {lw} before its read at {L}")
        else:
            # collection read snapshotted at level Ls (== the reader's level
            # for direct reads; earlier for pass-through forwarding).  The
            # snapshot must survive until gathered at L, and an in-place
            # *scratch* version parked on the row before Ls must never be
            # visible — the source program still sees the pristine tile
            # there (earlier collection writes ARE visible: the unrolled /
            # dynamic ordering semantics).
            Ls = ver[1]
            for lw, _, scratch in writes.get(row, ()):
                if Ls <= lw < L:
                    raise LoweringError(
                        f"store row {row}: snapshot taken at level {Ls} "
                        f"overwritten at {lw} before its read at {L}")
                if scratch and lw < Ls:
                    raise LoweringError(
                        f"store row {row}: scratch version written at level "
                        f"{lw} would be visible to the collection read at "
                        f"{Ls}")
    dirty: list[tuple[str, int]] = []
    for w, sl in scratch_last.items():
        dl = data_last.get(w, -1)
        if dl < 0:
            # scratch-only row: restore at the end (synthetic NEW stores
            # are exempt — their post-run content is never observed)
            if w[0] not in stores.scratch:
                dirty.append(w)
        elif sl > dl:
            raise LoweringError(
                f"store row {w}: in-place write at level {sl} after the "
                f"final collection write at {dl}")
    dirty_by_name: dict[str, np.ndarray] = {}
    for name, grp in itertools.groupby(sorted(dirty), key=lambda w: w[0]):
        dirty_by_name[name] = np.array([r for _, r in grp], np.int32)

    return _WFPlan(plans, dirty_by_name, levels)


def _group_plans(plans, infos, xlate: Callable | None = None):
    """Group per-task plans into ONE batched kernel call per (wavefront,
    class, source-signature) and build the gather/scatter specs.  Returns
    ``{level: [spec, ...]}``; ``xlate(store, row) -> row`` remaps global
    store rows (the region emission compacts each region onto local
    row-slices; identity for the whole-pool program)."""
    if xlate is None:
        xlate = lambda name, row: row           # noqa: E731
    by_level: dict[int, dict[tuple, list]] = {}
    for node, L, cname, key, in_plan, out_plan in plans:
        sig = (cname,
               tuple(ip if ip[0] in ("none", "new") else ("row", ip[1])
                     for ip in in_plan),
               tuple((p[0] if p else None, tuple(n for n, _ in ex), w)
                     for p, ex, w in out_plan))
        by_level.setdefault(L, {}).setdefault(sig, []).append(
            (in_plan, out_plan))

    level_specs: dict[int, list] = {}
    for L in sorted(by_level):
        specs = []
        for sig, members in by_level[L].items():
            cname = sig[0]
            info = infos[cname]
            G = len(members)
            # per data flow: None | (name, kind, arg) with kind "const"
            # (one row feeds the whole group), "range" (contiguous rows:
            # a static slice, cheaper than a gather), "gather", or "new"
            # (zeros of a static shape synthesized inline)
            gathers = []
            for fj in range(len(info.data_flows)):
                ip0 = members[0][0][fj]
                if ip0[0] == "none":
                    gathers.append(None)
                    continue
                if ip0[0] == "new":
                    gathers.append(("", "new", (ip0[1], ip0[2])))
                    continue
                name = ip0[1]
                rows = np.array([xlate(name, m[0][fj][2])
                                 for m in members], np.int32)
                if (rows == rows[0]).all():
                    gathers.append((name, "const", int(rows[0])))
                elif (np.diff(rows) == 1).all():
                    gathers.append((name, "range", int(rows[0])))
                else:
                    gathers.append((name, "gather", rows))
            wi = {f.flow_index: j for j, f in enumerate(info.writable_flows)}
            scatters = []   # (name, rows array, src_kind, src_idx)
            for fj, f in enumerate(info.data_flows):
                _, _, writable = members[0][1][fj]
                if writable:
                    n_tgt = 1 + len(members[0][1][fj][1])
                    for t in range(n_tgt):
                        name = (members[0][1][fj][0] if t == 0
                                else members[0][1][fj][1][t - 1])[0]
                        rows = np.array(
                            [xlate(name,
                                   (m[1][fj][0] if t == 0
                                    else m[1][fj][1][t - 1])[1])
                             for m in members], np.int32)
                        scatters.append((name, rows, "out", wi[f.flow_index]))
                else:
                    for t in range(len(members[0][1][fj][1])):
                        name = members[0][1][fj][1][t][0]
                        rows = np.array(
                            [xlate(name, m[1][fj][1][t][1])
                             for m in members], np.int32)
                        scatters.append((name, rows, "in", fj))
            specs.append((info.kernel.apply, gathers, scatters, G))
        level_specs[L] = specs
    return level_specs


def _build_wavefront(tp, infos, stores: _Stores):
    """The whole-pool wavefront emission: one program over the full task
    DAG, O(levels·classes) XLA ops.  Within one wavefront all tasks are
    independent (levels are longest-path: every dep edge strictly crosses
    levels), so each level executes as *gather-all → compute groups →
    scatter-all* — snapshot semantics that make the level's result
    independent of group ordering.  A whole Cholesky trailing update
    becomes one ``vmap``-batched tile matmul on the MXU (the compiled
    analog of the reference keeping a GPU stream saturated across a
    panel, ``jdf2c.c:6566``, ``device_gpu.c:2522-2531``).
    """
    wf = _wavefront_plan(tp, infos, stores)
    level_specs = _group_plans(wf.plans, infos)
    dirty_by_name = wf.dirty_by_name

    # ---- emission ----------------------------------------------------------
    runs = _fold_runs(level_specs)
    scan_min = _params.get("lowering_scan_min")
    step_fn = _make_step(runs, dirty_by_name, scan_min)
    sig = ("wavefront", scan_min, _freeze(dirty_by_name), _freeze_runs(runs))
    return step_fn, sig


def _apply_scatters(arr, entries):
    """Apply one level's scatters to one store as a SINGLE update.
    Separate ``.at[].set`` calls each copy the whole store; merging
    them (and lowering contiguous row sets to a static slice update —
    full-coverage levels like a stencil sweep become a plain slab
    assignment) keeps the per-level cost at the data actually moved."""
    import jax.numpy as jnp
    rows_all = np.concatenate([rows for rows, _, _ in entries])
    vals = []
    for rows, v, batched in entries:
        vals.append(v if batched
                    else jnp.broadcast_to(v, (len(rows),) + v.shape))
    v_all = vals[0] if len(vals) == 1 else jnp.concatenate(vals, axis=0)
    order = np.argsort(rows_all, kind="stable")
    srt = rows_all[order]
    if (np.diff(srt) == 1).all():
        if not (order == np.arange(len(order))).all():
            v_all = v_all[order]
        r0 = int(srt[0])
        return arr.at[r0:r0 + len(srt)].set(v_all)
    return arr.at[rows_all].set(v_all)


def _run_level(st: dict, specs) -> dict:
    import jax
    import jax.numpy as jnp
    st = dict(st)
    pend: dict[str, list] = {}           # scatters applied level-atomic
    for apply, gathers, scatters, G in specs:
        args, axes = [], []
        for gth in gathers:
            if gth is None:
                args.append(None)
                axes.append(None)
            elif gth[1] == "const":
                args.append(st[gth[0]][gth[2]])
                axes.append(None)
            elif gth[1] == "range":
                args.append(st[gth[0]][gth[2]:gth[2] + G])
                axes.append(0)
            elif gth[1] == "new":
                shape, dtype = gth[2]
                args.append(jnp.zeros(shape, dtype))
                axes.append(None)
            else:
                args.append(st[gth[0]][gth[2]])
                axes.append(0)
        if G == 1 or all(ax is None for ax in axes):
            res = apply(*args)
            res = res if isinstance(res, tuple) else (res,)
            out_batched = False
        else:
            def tup_apply(*a):
                r = apply(*a)
                return r if isinstance(r, tuple) else (r,)
            res = jax.vmap(tup_apply, in_axes=tuple(axes))(*args)
            out_batched = True
        for name, rows, src_kind, src_idx in scatters:
            if src_kind == "out":
                v, batched = res[src_idx], out_batched
            else:
                v, batched = args[src_idx], axes[src_idx] == 0
            if not batched and len(rows) == 1 and v is not None:
                v = v[None]
                batched = True
            pend.setdefault(name, []).append((rows, v, batched))
    for name, entries in pend.items():
        st[name] = _apply_scatters(st[name], entries)
    return st


# ---- uniform-run folding (compile-cost control) ---------------------------
# Consecutive levels with FULLY IDENTICAL specs — same kernels, same
# group sizes, same gather/scatter kinds AND row indices (a stencil
# sweep's T iterations; never a shrinking factorization panel) —
# become ONE lax.scan body: identical per-iteration ops, O(1) trace/
# compile cost instead of O(levels).  VERDICT r4 weak #2 named the
# O(wavefronts x classes) op count as the likely next compile wall.
def _spec_eq(a, b) -> bool:
    if len(a) != len(b):
        return False
    for (ap, ag, as_, aG), (bp, bg, bs, bG) in zip(a, b):
        if ap is not bp or aG != bG or len(ag) != len(bg) \
                or len(as_) != len(bs):
            return False
        for x, y in zip(ag, bg):
            if (x is None) != (y is None):
                return False
            if x is None:
                continue
            if x[0] != y[0] or x[1] != y[1]:
                return False
            if x[1] == "new":
                if x[2] != y[2]:
                    return False
            elif not np.array_equal(x[2], y[2]):
                return False
        for x, y in zip(as_, bs):
            if x[0] != y[0] or x[2] != y[2] or x[3] != y[3] \
                    or not np.array_equal(x[1], y[1]):
                return False
    return True


def _fold_runs(level_specs: dict[int, list]) -> list[tuple[Any, int]]:
    runs: list[tuple[Any, int]] = []        # (specs, repeat count)
    for L in sorted(level_specs):
        specs = level_specs[L]
        if runs and _spec_eq(runs[-1][0], specs):
            runs[-1] = (runs[-1][0], runs[-1][1] + 1)
        else:
            runs.append((specs, 1))
    return runs


def _freeze_runs(runs) -> tuple:
    return tuple(
        (reps, tuple((apply, _freeze(gathers), _freeze(scatters), G)
                     for apply, gathers, scatters, G in specs))
        for specs, reps in runs)


def _make_step(runs, dirty_by_name: dict[str, np.ndarray],
               scan_min: int) -> Callable:
    def step_fn(st: dict) -> dict:
        import jax
        st = dict(st)
        saved = {name: st[name][rows]
                 for name, rows in dirty_by_name.items()}
        for specs, reps in runs:
            if reps < scan_min:
                for _ in range(reps):
                    st = _run_level(st, specs)
            else:
                def body(carry, _x, _s=specs):
                    return _run_level(carry, _s), None
                st, _ = jax.lax.scan(body, st, None, length=reps)
        for name, rows in dirty_by_name.items():
            st[name] = st[name].at[rows].set(saved[name])
        return st

    return step_fn


# ---------------------------------------------------------------------------
# pass 3: generic unrolled dataflow (topological trace)
# ---------------------------------------------------------------------------

def _task_graph(tp, infos):
    """Concrete task DAG (CTL edges count): returns ``(order, levels)`` —
    a Kahn topological order over ``(cname, i)`` nodes and each node's
    *wavefront level* (longest path from a source; an edge always crosses
    levels strictly, so same-level tasks are mutually independent)."""
    index: dict[tuple[str, tuple], tuple[str, int]] = {}
    for cname, info in infos.items():
        for i, loc in enumerate(info.tasks):
            index[(cname, info.tc.make_key(loc))] = (cname, i)
    indeg = {v: 0 for v in index.values()}
    succs: dict[tuple[str, int], list] = {v: [] for v in index.values()}
    for cname, info in infos.items():
        for i, loc in enumerate(info.tasks):
            for f in info.tc.flows:
                for d in f.deps_out:
                    if d.target_class is None or not d.active(loc):
                        continue
                    tgt_tc = tp.task_class(d.target_class)
                    for tgt_loc in d.each_target(loc):
                        tgt = index.get(
                            (d.target_class, tgt_tc.make_key(tgt_loc)))
                        if tgt is None:
                            if tgt_tc.in_space is not None \
                                    and not tgt_tc.in_space(tgt_loc):
                                continue   # out-of-space edge: the
                                # generated bounds check drops it
                            raise LoweringError(
                                f"{cname}{info.tc.make_key(loc)} -> missing "
                                f"successor {d.target_class}({tgt_loc})")
                        succs[(cname, i)].append(tgt)
                        indeg[tgt] += 1
    ready = [v for v, n in indeg.items() if n == 0]
    levels = {v: 0 for v in ready}
    out = []
    while ready:
        v = ready.pop()
        out.append(v)
        for s in succs[v]:
            levels[s] = max(levels.get(s, 0), levels[v] + 1)
            indeg[s] -= 1
            if indeg[s] == 0:
                ready.append(s)
    if len(out) != len(indeg):
        raise LoweringError("task graph has a cycle")
    return out, levels


def _topo_order(tp, infos) -> list[tuple[str, int]]:
    return _task_graph(tp, infos)[0]


def _build_unrolled(tp, infos, stores: _Stores):
    order = _topo_order(tp, infos)

    # precompute, per task, its input plan and output plan (host side)
    plans = []
    for cname, i in order:
        info = infos[cname]
        tc, loc = info.tc, info.tasks[i]
        key = tc.make_key(loc)
        # per data flow: ("store", name, row) | ("val", ck) | ("none",)
        # | ("new", shape, dtype)
        in_plan = []
        for f in info.data_flows:
            deps = _active_in_deps(f, loc)
            if len(deps) > 1:
                raise LoweringError(
                    f"{cname}{key} flow {f.name}: expected at most one "
                    f"active input dep, got {len(deps)}")
            if not deps or deps[0].null:
                in_plan.append(("none",))
                continue
            d = deps[0]
            if d.data_ref is not None:
                dc, k = d.data_ref(loc)
                in_plan.append(("store", dc.name, stores.row(dc, _norm_key(k))))
            elif d.target_class is None:
                dtt = d.dtt or f.dtt
                in_plan.append(("new", tuple(dtt.shape),
                                str(np.dtype(dtt.dtype))))
            else:
                ptc = tp.task_class(d.target_class)
                pkey = ptc.make_key(d.target_params(loc))
                pfi = next(ff.flow_index for ff in ptc.flows
                           if ff.name == d.target_flow)
                in_plan.append(("val", (d.target_class, pkey, pfi)))
        out_plan = []       # per data flow: list of store rows to scatter
        for f in info.data_flows:
            rows = []
            for d in _active_out_deps(f, loc):
                if d.data_ref is not None:
                    dc, k = d.data_ref(loc)
                    rows.append((dc.name, stores.row(dc, _norm_key(k))))
                    stores.written.add(dc.name)
            out_plan.append(rows)
        plans.append((cname, key, info, in_plan, out_plan))

    def step_fn(st: dict) -> dict:
        import jax.numpy as jnp
        st = dict(st)
        vals: dict[tuple, Any] = {}
        for cname, key, info, in_plan, out_plan in plans:
            args = []
            for kind, *ref in in_plan:
                if kind == "store":
                    name, row = ref
                    args.append(st[name][row])
                elif kind == "none":
                    args.append(None)
                elif kind == "new":
                    args.append(jnp.zeros(ref[0], ref[1]))
                else:
                    args.append(vals[ref[0]])
            if info.kernel is not None and args:
                res = info.kernel.apply(*args)
                if not isinstance(res, tuple):
                    res = (res,)
                wi = {f.flow_index: j
                      for j, f in enumerate(info.writable_flows)}
            else:
                res, wi = (), {}
            for f, rows in zip(info.data_flows, out_plan):
                v = (res[wi[f.flow_index]] if f.flow_index in wi
                     else args[info.data_flows.index(f)])
                vals[(cname, key, f.flow_index)] = v
                for name, row in rows:
                    st[name] = st[name].at[row].set(v)
        return st

    sig = ("unrolled", tuple(
        (cname, key,
         info.kernel.apply if info.kernel is not None else None,
         tuple(f.flow_index for f in info.data_flows),
         tuple(f.flow_index for f in info.writable_flows),
         _freeze(in_plan), _freeze(out_plan))
        for cname, key, info, in_plan, out_plan in plans))
    return step_fn, sig


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------

class LoweredTaskpool:
    """A compiled incarnation of a PTG taskpool.

    ``step_fn``: pure function ``{collection_name: stacked tiles} -> same`` —
    one full taskpool execution; jit it, scan it, shard it.
    ``execute()``: convenience — run once on device and write tiles back to
    the source collections (the dynamic path's completion semantics).

    With ``mesh`` set (multi-rank lowering), execution jits with
    ``in_shardings``/``out_shardings`` derived from the collections' own
    distributions (:meth:`shardings`): every tile lives on the device its
    ``rank_of`` names, and GSPMD inserts the collectives that the dynamic
    runtime's remote-dep protocol would have performed — the compiled
    incarnation of SURVEY §7's "parallelism is a derived schedule on the
    dataflow core".
    """

    def __init__(self, tp, step_fn, stores: _Stores, mode: str,
                 mesh: Any = None, signature: Any = None) -> None:
        self.taskpool = tp
        self.step_fn = step_fn
        self._stores = stores
        self.mode = mode    # "chain-collapse" | "wavefront" | "unrolled"
        self.mesh = mesh    # jax Mesh with a "ranks" axis, or None
        self.signature = signature   # structural key; None = uncacheable
        self._jitted = None

    def jitted(self):
        """The jit-wrapped step function — shared process-wide through
        :data:`lowering_cache` when the lowering carries a signature, so
        re-lowering a structurally identical taskpool skips trace AND
        compile (jax.jit re-traces per input aval under the shared
        wrapper, so differing tile shapes stay correct)."""
        if self._jitted is not None:
            return self._jitted
        ensure_compile_cache()
        import jax

        def build():
            if self.mesh is not None:
                sh = self.shardings()
                return jax.jit(self.step_fn, in_shardings=(sh,),
                               out_shardings=sh)
            return jax.jit(self.step_fn)

        key = None
        if self.signature is not None and _params.get("lowering_cache"):
            # the mesh object hashes by devices+axes: a same-shape mesh on
            # different devices can never false-hit; the backend triple
            # (jax version, backend, device kind) keeps a cache consulted
            # across a JAX_PLATFORMS flip — or a compile-cache dir shared
            # by CPU and TPU processes — from serving a stale executable
            key = (self.mode, self.mesh, _backend_signature(),
                   tuple(sorted(self._stores.replicated)), self.signature)
        self._jitted = lowering_cache.get_or_build(key, build)
        return self._jitted

    def initial_stores(self) -> dict[str, Any]:
        return self._stores.materialize()

    @property
    def written_collections(self) -> set[str]:
        return set(self._stores.written)

    def shardings(self) -> dict[str, Any]:
        """Per-store NamedSharding over the ``ranks`` mesh axis: rank-major
        stacked stores shard axis 0 (each slab on its owner), replicated
        (nodes==1) collections replicate."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        assert self.mesh is not None
        out = {}
        for name in self._stores.dcs:
            spec = P() if name in self._stores.replicated else P("ranks")
            out[name] = NamedSharding(self.mesh, spec)
        return out

    def warm(self) -> dict[str, float]:
        """AOT trace + compile against abstract avals — no tile is
        materialized or moved and nothing executes.  Populates JAX's
        persistent compilation cache (and warms this process's jit
        wrapper tracing path), so a later bench stage or a fresh process
        pays deserialization, not a full XLA compile (the BENCH_r04/r05
        rc-124 shape).  The cache-warming CLI (``python -m
        parsec_tpu.ptg.lowering --warm``) drives this."""
        ensure_compile_cache()
        jf = self.jitted()
        avals = self._stores.avals()
        t0 = time.perf_counter()
        lowered = jf.lower(avals)
        t1 = time.perf_counter()
        lowered.compile()
        return {"trace_s": round(t1 - t0, 4),
                "compile_s": round(time.perf_counter() - t1, 4)}

    def execute(self) -> dict[str, Any]:
        from ..prof.profiling import profiling
        self.jitted()
        # one trace span per compiled execution (the lowered analog of the
        # task_profiler's exec phase): the fast path stays observable
        keys = None
        if profiling.enabled:
            keys = profiling.add_dictionary_keyword(
                "lowered_execute", "#00aaff", ("taskpool", "mode"))
            profiling.trace(keys[0], object_id=id(self),
                            info={"taskpool": self.taskpool.name,
                                  "mode": self.mode})
        out = self._jitted(self.initial_stores())
        _note_xla_calls(1)          # one program, one dispatch
        self._stores.writeback(out)
        if keys is not None:
            profiling.trace(keys[1], object_id=id(self))
        return out


def lower_taskpool(tp, context: Any = None, mesh: Any = None,
                   passes: str = "auto") -> LoweredTaskpool:
    """Lower a regular PTG taskpool to one XLA program.

    ``mesh``: a :class:`jax.sharding.Mesh` with one ``"ranks"`` axis — lowers
    the *distributed* taskpool to a single SPMD program over that mesh, tile
    ownership taken from each collection's ``rank_of`` (the distribution the
    dynamic runtime would route remote deps by).

    ``passes``: ``"auto"`` tries chain-collapse → wavefront → unrolled (most
    specialized first); or force one of ``"chain-collapse"``, ``"wavefront"``,
    ``"unrolled"`` (testing / benchmarking individual emissions).

    Raises :class:`LoweringError` when the structure is not lowerable; the
    caller then runs the dynamic scheduler instead (same taskpool object).
    """
    nranks = None
    if mesh is not None:
        axes = dict(getattr(mesh, "shape", {}))
        if list(axes) != ["ranks"]:
            raise LoweringError(
                f"multi-rank lowering needs a 1-D mesh with a 'ranks' axis, "
                f"got {list(axes)}")
        nranks = axes["ranks"]
    elif context is not None and getattr(context, "nb_ranks", 1) > 1:
        raise LoweringError("multi-rank lowering needs an explicit mesh= "
                            "(see lower_taskpool docstring); dynamic path "
                            "here")
    infos = _analyze(tp)
    if passes not in ("auto", "chain-collapse", "wavefront", "unrolled"):
        raise ValueError(f"unknown lowering pass {passes!r}")

    if passes in ("auto", "chain-collapse"):
        stores = _Stores(nranks)
        built = _try_chain_collapse(tp, infos, stores)
        if built is not None:
            step, sig = built
            return LoweredTaskpool(tp, step, stores, "chain-collapse",
                                   mesh=mesh, signature=sig)
        if passes == "chain-collapse":
            raise LoweringError("taskpool does not chain-collapse")
    if passes in ("auto", "wavefront"):
        stores = _Stores(nranks)
        try:
            step, sig = _build_wavefront(tp, infos, stores)
            return LoweredTaskpool(tp, step, stores, "wavefront", mesh=mesh,
                                   signature=sig)
        except LoweringError:
            if passes == "wavefront":
                raise
    stores = _Stores(nranks)
    step, sig = _build_unrolled(tp, infos, stores)
    return LoweredTaskpool(tp, step, stores, "unrolled", mesh=mesh,
                           signature=sig)


# ---------------------------------------------------------------------------
# megakernel regions (MPK): one jitted program per verified subgraph,
# runtime scheduling only at region boundaries, under a compile budget
# ---------------------------------------------------------------------------

class LoweredRegion:
    """One convex subregion of a taskpool, lowered to one program.

    The program is a pure function over *region-local row slices*: the
    runtime boundary gathers the rows the region touches from the shared
    host table, calls the compiled executable (or, for budget-shed
    regions, the same step function eagerly, op by op), and scatters the
    written rows back — deps, comm, and device staging live entirely at
    this boundary, exactly the MPK contract."""

    __slots__ = ("index", "ntasks", "level_lo", "level_hi", "step_fn",
                 "signature", "touched", "written", "avals", "preds",
                 "succs", "eager", "compiled", "compile_s", "trace_s",
                 "_exec")

    def __init__(self, index: int, ntasks: int, level_lo: int,
                 level_hi: int, step_fn: Callable | None, signature: Any,
                 touched: dict[str, np.ndarray],
                 written: dict[str, tuple[np.ndarray, np.ndarray]],
                 avals: dict[str, Any]) -> None:
        self.index = index
        self.ntasks = ntasks
        self.level_lo = level_lo
        self.level_hi = level_hi
        self.step_fn = step_fn          # None: CTL-only region (no data)
        self.signature = signature
        self.touched = touched          # store -> global rows gathered
        self.written = written          # store -> (global rows, local rows)
        self.avals = avals
        self.preds: set[int] = set()    # region deps (task + row-conflict)
        self.succs: set[int] = set()
        self.eager = False              # budget-shed: run uncompiled
        self.compiled = False
        self.compile_s = 0.0
        self.trace_s = 0.0
        self._exec = None

    def __repr__(self) -> str:
        state = ("compiled" if self.compiled
                 else "eager" if self.eager else "cold")
        return (f"<LoweredRegion {self.index}: {self.ntasks} tasks, "
                f"levels {self.level_lo}..{self.level_hi}, {state}>")


class RegionLoweredTaskpool:
    """A taskpool lowered to a DAG of megakernel regions.

    ``compile(budget_s=)`` stages compilation region by region (smallest
    first, so measured cost guards the big compiles) under the
    wall-clock budget — regions the budget cannot afford fall back to
    the eager path, so a compile can never eat a bench stage's deadline
    (BENCH_r04/r05, rc 124).  ``taskpool()``
    builds a PTG pool with ONE task per region (ranged CTL fan-in edges
    mirroring the region DAG) — the runtime schedules regions exactly
    like tasks: deps, priorities, worker concurrency, flight recorder.
    ``execute()`` is the convenience wrapper: materialize the shared
    row table, run the region pool on a Context, write tiles back."""

    def __init__(self, tp, stores: _Stores, regions: list[LoweredRegion],
                 dirty_by_name: dict[str, np.ndarray]) -> None:
        self.source = tp            # the task-grained pool this lowers
        self.mode = "region"
        self._stores = stores
        self.regions = regions
        self.dirty_by_name = dirty_by_name
        self._lock = threading.Lock()
        self._compile_done = False
        self._dirty_saved: dict[str, np.ndarray] = {}
        self._finalized = True
        self.xla_calls = 0          # compiled-program invocations (lifetime)
        self.eager_runs = 0

    # -- compile budget ------------------------------------------------------
    def _cache_key(self, reg: LoweredRegion):
        if not _params.get("lowering_cache"):
            return None
        shapes = tuple(sorted((nm, tuple(a.shape), str(a.dtype))
                              for nm, a in reg.avals.items()))
        return ("region", _backend_signature(), shapes, reg.signature)

    def compile(self, budget_s: float | None = None,
                note: Callable | None = None) -> dict:
        """Staged AOT compilation, SMALLEST region first.

        ``budget_s`` defaults to the ``lowering_compile_budget_s`` MCA
        param (0 = unbudgeted).  The budget is enforced *between*
        compiles: before each region the spent wall clock plus a
        per-task cost estimate (measured from the regions already
        compiled) must fit, else the region is shed to the eager path.
        Ascending size order is what makes the estimate load-bearing —
        the cheap compiles bootstrap the rate that guards the expensive
        ones, so the largest region is shed BEFORE burning the budget,
        never after (largest-first would run the most dangerous compile
        while the rate is still 0).  An XLA compile cannot be aborted
        mid-flight, so the one unguarded compile is the smallest region;
        ``lowering_region_max_tasks`` is what bounds the worst single
        compile.  Cache hits are free and never shed — a warm process
        compiles nothing.  ``note(**kw)`` receives one progress record
        per region (so a caller under a deadline can name the region that
        was compiling when it died)."""
        import jax
        ensure_compile_cache()
        if budget_s is None:
            b = _params.get("lowering_compile_budget_s")
            budget_s = float(b) if b and b > 0 else None
        t_start = time.perf_counter()
        rate = 0.0                  # measured compile seconds per task
        for reg in sorted(self.regions, key=lambda r: r.ntasks):
            if reg.step_fn is None or reg.compiled or reg._exec is not None:
                continue
            key = self._cache_key(reg)
            cached = lowering_cache.peek(key)
            if cached is not None:
                # a warm region re-registers as a hit; *_compile_s ~ 0
                reg._exec = lowering_cache.get_or_build(key, lambda: cached)
                reg.compiled, reg.eager = True, False
                reg.compile_s = reg.trace_s = 0.0
                if note is not None:
                    note(region=reg.index, ntasks=reg.ntasks,
                         compile_s=0.0, cached=True)
                continue
            if budget_s is not None:
                remaining = budget_s - (time.perf_counter() - t_start)
                if remaining <= 0 or rate * reg.ntasks > remaining:
                    reg.eager = True
                    if note is not None:
                        note(region=reg.index, ntasks=reg.ntasks,
                             eager=True, budget_s=budget_s)
                    continue
            if note is not None:
                note(region=reg.index, ntasks=reg.ntasks, compiling=True)

            def build(reg=reg):
                jf = jax.jit(reg.step_fn)
                t0 = time.perf_counter()
                lowered = jf.lower(reg.avals)
                reg.trace_s = time.perf_counter() - t0
                t1 = time.perf_counter()
                compiled = lowered.compile()
                reg.compile_s = time.perf_counter() - t1
                return compiled

            reg._exec = lowering_cache.get_or_build(key, build)
            reg.compiled, reg.eager = True, False
            if reg.ntasks:
                rate = max(rate, (reg.compile_s + reg.trace_s) / reg.ntasks)
            if note is not None:
                note(region=reg.index, ntasks=reg.ntasks,
                     compile_s=round(reg.compile_s, 4),
                     trace_s=round(reg.trace_s, 4))
        self._compile_done = True
        return self.stats()

    def stats(self) -> dict:
        data_regions = [r for r in self.regions if r.step_fn is not None]
        return {
            "regions": len(self.regions),
            "regions_compiled": sum(r.compiled for r in data_regions),
            "regions_eager": sum(r.eager for r in data_regions),
            "ntasks": sum(r.ntasks for r in self.regions),
            "trace_s": round(sum(r.trace_s for r in data_regions), 4),
            "compile_s": round(sum(r.compile_s for r in data_regions), 4),
            "xla_calls": self.xla_calls,
            "eager_runs": self.eager_runs,
        }

    # -- execution -----------------------------------------------------------
    def materialize_table(self) -> dict[str, np.ndarray]:
        """The shared host row table regions gather from / scatter into.
        Mutable numpy (regions write disjoint rows, ordered by the region
        DAG); dirty rows — in-place value homes the source program never
        writes back — are snapshotted for restore at finalize."""
        table = {nm: np.array(v)
                 for nm, v in self._stores.materialize().items()}
        self._dirty_saved = {nm: table[nm][rows].copy()
                             for nm, rows in self.dirty_by_name.items()}
        self._finalized = False
        return table

    def run_region(self, r: int, table: dict[str, np.ndarray]) -> None:
        """Execute region ``r`` against the shared table: gather touched
        rows, run the compiled program (ONE XLA dispatch) or the eager
        step, scatter written rows back.  This is the region task's body
        — what a worker thread runs when the scheduler releases it."""
        reg = self.regions[r]
        if reg.step_fn is None:
            return
        inputs = {nm: table[nm][rows] for nm, rows in reg.touched.items()}
        if reg._exec is not None:
            out = reg._exec(inputs)
            with self._lock:
                self.xla_calls += 1
            _note_xla_calls(1)
        else:
            import jax.numpy as jnp
            out = reg.step_fn({nm: jnp.asarray(v)
                               for nm, v in inputs.items()})
            with self._lock:
                self.eager_runs += 1
        for nm, (grows, lrows) in reg.written.items():
            table[nm][grows] = np.asarray(out[nm])[lrows]

    def taskpool(self, table: dict[str, np.ndarray]):
        """Build the schedulable region pool: one REGION(r) task per
        region, the region DAG as ranged CTL fan-in/fan-out edges — a
        plain PTG pool, so graphcheck verifies it and the runtime
        (Context, RuntimeServer) schedules it like any other.  Completion
        finalizes the table back into the source collections."""
        from . import dsl
        preds = tuple(tuple(sorted(r.preds)) for r in self.regions)
        succs = tuple(tuple(sorted(r.succs)) for r in self.regions)
        p = dsl.PTGBuilder(f"{self.source.name}_regions",
                           NR=len(self.regions), RPRED=preds, RSUCC=succs)
        t = p.task("REGION", r=dsl.span(0, lambda g, l: g.NR - 1))
        f = t.flow("ctl", dsl.CTL)
        f.input(pred=("REGION", "ctl",
                      lambda g, l: [{"r": q} for q in g.RPRED[l.r]]),
                guard=lambda g, l: bool(g.RPRED[l.r]), ranged=True)
        f.output(succ=("REGION", "ctl",
                       lambda g, l: [{"r": q} for q in g.RSUCC[l.r]]),
                 guard=lambda g, l: bool(g.RSUCC[l.r]))
        # earlier wavefront bands first: the region-grain critical path
        t.priority(lambda g, l: -self.regions[l.r].level_lo)
        plan = self

        def body(es: Any, task: Any, g: Any, l: Any) -> None:
            plan.run_region(l.r, table)

        t.body(body)
        pool = p.build()
        pool.region_plan = self
        pool.add_completion_listener(lambda _tp: self.finalize(table))
        return pool

    def finalize(self, table: dict[str, np.ndarray]) -> None:
        """Restore dirty rows (scratch homes the source program never
        writes back) and write the table's tiles into the collections
        with version bumps — the dynamic path's completion semantics.
        Idempotent: fires from the pool completion listener AND from
        explicit callers."""
        with self._lock:
            if self._finalized:
                return
            self._finalized = True
        for nm, rows in self.dirty_by_name.items():
            table[nm][rows] = self._dirty_saved[nm]
        self._stores.writeback(table)

    def execute(self, context: Any = None, timeout: float = 300.0,
                budget_s: float | None = None) -> dict[str, np.ndarray]:
        """Compile (under the budget), run the region pool to completion,
        write back.  With ``context=`` the pool rides a live runtime
        (worker threads execute independent regions concurrently); bare
        calls drive an ephemeral single-threaded Context."""
        if not self._compile_done:
            self.compile(budget_s=budget_s)
        table = self.materialize_table()
        pool = self.taskpool(table)
        if context is not None:
            context.add_taskpool(pool)
            pool.wait(timeout=timeout)
        else:
            from ..runtime import Context
            ctx = Context(nb_cores=0)
            try:
                ctx.add_taskpool(pool)
                ctx.wait(timeout=timeout)
            finally:
                ctx.fini()
        self.finalize(table)        # no-op when the listener already ran
        return table


def _note_xla_calls(n: int) -> None:
    """Feed the process-wide XLA dispatch ledger (device/device.py) so
    the region path and the dynamic device path share ONE counter — the
    XLA-calls-per-DAG bench axis reads it for both."""
    try:
        from ..device.device import note_xla_calls
        note_xla_calls(n)
    except Exception:
        pass


def _written_rows(out_plan) -> list[tuple[str, int]]:
    """Every (store, row) a task's out_plan writes — extras plus the
    writable primary.  ONE home for this extraction: the region
    anti-dependency ledger and the per-region written-set builder must
    agree on it, or the region DAG under-orders the writebacks."""
    rows: list[tuple[str, int]] = []
    for primary, extras, writable in out_plan:
        rows.extend(extras)
        if writable and primary is not None:
            rows.append(primary)
    return rows


def lower_regions(tp, context: Any = None, max_tasks: int | None = None,
                  report: Any = None) -> RegionLoweredTaskpool:
    """Lower an irregular PTG taskpool to a DAG of megakernel regions.

    Region selection is driven by graphcheck's *verified* execution
    space: the pool is statically checked (``analysis.check_ptg``) and
    its concrete task graph carved into convex wavefront-level bands per
    weakly-connected component (``analysis.regions``), at most
    ``max_tasks`` tasks each (default: the ``lowering_region_max_tasks``
    MCA param).  Each region lowers to one jitted program over its local
    store-row slices via the same grouped-vmapped wavefront emission as
    the whole-pool pass — program size stays O(wavefronts·classes), not
    O(tasks).  Cross-region dataflow resolves through the shared row
    table; row-level conflicts that task edges alone would not order
    (cross-component collection reads/writes) become extra region-DAG
    edges, so region scheduling can never hide a WAR/WAW hazard the
    whole-pool pass proves ordered.

    Raises :class:`LoweringError` (or ``analysis.GraphCheckError``) when
    the pool cannot be region-lowered; callers fall back to
    :func:`lower_taskpool` or the dynamic runtime.
    """
    if context is not None and getattr(context, "nb_ranks", 1) > 1:
        raise LoweringError("region lowering is single-rank; use "
                            "lower_taskpool(mesh=...) for SPMD lowering")
    from ..analysis import check_ptg
    if report is None:
        report = check_ptg(tp)
    if max_tasks is None:
        max_tasks = _params.get("lowering_region_max_tasks")
    try:
        regs = report.select_regions(max_tasks=max_tasks)
    except ValueError as e:
        # a truncated enumeration (analysis_max_tasks) cannot produce
        # sound regions — surface it under this function's documented
        # exception contract so callers' fallback paths engage
        raise LoweringError(str(e))

    infos = _analyze(tp)
    stores = _Stores()
    wf = _wavefront_plan(tp, infos, stores)
    scan_min = _params.get("lowering_scan_min")

    assign: dict[tuple, int] = {}
    for r in regs:
        for node in r.members:
            assign[node] = r.index

    plans_by_region: list[list] = [[] for _ in regs]
    # row-access ledger for conflict ordering: row -> [(region, level, w)]
    accesses: dict[tuple, list[tuple[int, int, bool]]] = {}
    for plan in wf.plans:
        node, L, cname, key, in_plan, out_plan = plan
        try:
            ri = assign[(cname, key)]
        except KeyError:
            raise LoweringError(
                f"{cname}{key}: enumerated by the lowering but absent "
                f"from graphcheck's execution space")
        plans_by_region[ri].append(plan)
        for ip in in_plan:
            if ip[0] == "row":
                accesses.setdefault((ip[1], ip[2]), []).append(
                    (ri, L, False))
        for w in _written_rows(out_plan):
            accesses.setdefault(w, []).append((ri, L, True))

    # ---- region DAG: task edges + row-conflict ordering edges --------------
    preds = [set(r.preds) for r in regs]
    succs = [set(r.succs) for r in regs]

    def add_edge(a: int, b: int) -> None:
        if a != b:
            succs[a].add(b)
            preds[b].add(a)

    for row, acc in accesses.items():
        writes = [(ri, L) for ri, L, w in acc if w]
        if not writes:
            continue
        for wri, wl in writes:
            for ri, L, w in acc:
                if ri == wri:
                    continue
                if L > wl:
                    add_edge(wri, ri)       # write before later access
                elif L < wl:
                    add_edge(ri, wri)       # earlier access before write
                elif not w:
                    # same wavefront, different regions: snapshot
                    # semantics say the reader sees the PRE-level value,
                    # so the reader must run first (anti-dependency)
                    add_edge(ri, wri)
    # acyclicity of the combined region DAG (task edges alone are acyclic
    # by construction; anti-dependency edges can, in principle, close a
    # cycle — then region granularity cannot honor snapshot semantics)
    indeg = [len(p) for p in preds]
    ready = [i for i, n in enumerate(indeg) if n == 0]
    seen = 0
    while ready:
        i = ready.pop()
        seen += 1
        for s in succs[i]:
            indeg[s] -= 1
            if indeg[s] == 0:
                ready.append(s)
    if seen != len(regs):
        raise LoweringError(
            "region ordering cycle: row-conflict anti-dependencies are "
            "not satisfiable at region granularity (dynamic path)")

    # ---- per-region emission: local row slices, grouped vmapped levels -----
    regions: list[LoweredRegion] = []
    for r, rplans in zip(regs, plans_by_region):
        if not rplans:                      # CTL-only region: ordering only
            regions.append(LoweredRegion(
                r.index, r.ntasks, r.level_lo, r.level_hi,
                None, None, {}, {}, {}))
            continue
        touched_sets: dict[str, set[int]] = {}
        written_sets: dict[str, set[int]] = {}
        for node, L, cname, key, in_plan, out_plan in rplans:
            for ip in in_plan:
                if ip[0] == "row":
                    touched_sets.setdefault(ip[1], set()).add(ip[2])
            for nm, row in _written_rows(out_plan):
                touched_sets.setdefault(nm, set()).add(row)
                written_sets.setdefault(nm, set()).add(row)
        touched = {nm: np.array(sorted(rs), np.int64)
                   for nm, rs in sorted(touched_sets.items())}
        lmap = {nm: {g: i for i, g in enumerate(arr.tolist())}
                for nm, arr in touched.items()}
        level_specs = _group_plans(
            rplans, infos, xlate=lambda nm, g: lmap[nm][g])
        runs = _fold_runs(level_specs)
        step_fn = _make_step(runs, {}, scan_min)
        written = {}
        for nm, rs in sorted(written_sets.items()):
            grows = np.array(sorted(rs), np.int64)
            written[nm] = (grows,
                           np.array([lmap[nm][g] for g in grows.tolist()],
                                    np.int64))
        import jax
        avals = {nm: jax.ShapeDtypeStruct(
            (len(arr),) + stores.shape[nm], stores.dtype[nm])
            for nm, arr in touched.items()}
        # the signature covers ONLY what the traced program depends on:
        # the grouped runs (gather/scatter specs in region-LOCAL rows)
        # — the avals join it in the cache key.  Global touched rows are
        # boundary bookkeeping; folding them in would give structurally
        # identical regions (the LLM step's N parallel per-seq chains)
        # N distinct keys and N redundant compiles of one program.
        sig = ("region", scan_min, _freeze_runs(runs))
        regions.append(LoweredRegion(
            r.index, r.ntasks, r.level_lo, r.level_hi,
            step_fn, sig, touched, written, avals))
    for reg, p_, s_ in zip(regions, preds, succs):
        reg.preds, reg.succs = p_, s_
    return RegionLoweredTaskpool(tp, stores, regions, wf.dirty_by_name)


# ---------------------------------------------------------------------------
# AOT cache warming: pay compiles BEFORE a bench stage's clock starts
# ---------------------------------------------------------------------------

def _warm_workload(workload: str, n: int | None, nb: int | None):
    """Build one named workload's taskpool at the given geometry with
    ZERO-initialized tiles — warming traces against avals, so contents
    never matter and no bench-scale host RNG runs."""
    def zeros(*_a):
        def init(m, n_, shape):
            return np.zeros(shape, np.float32)
        return init

    if workload == "gemm":
        from ..data_dist.matrix import TiledMatrix
        from ..models.tiled_gemm import tiled_gemm_ptg
        n, nb = n or 16384, nb or 512
        import jax.numpy as jnp
        bf16 = np.dtype(jnp.bfloat16)
        A = TiledMatrix("A", n, n, nb, nb, dtype=bf16,
                        init_fn=lambda m, n_, s: np.zeros(s, bf16))
        B = TiledMatrix("B", n, n, nb, nb, dtype=bf16,
                        init_fn=lambda m, n_, s: np.zeros(s, bf16))
        C = TiledMatrix("C", n, n, nb, nb, dtype=np.float32,
                        init_fn=zeros())
        return tiled_gemm_ptg(A, B, C), dict(n=n, nb=nb)
    if workload == "cholesky":
        from ..data_dist.matrix import SymTwoDimBlockCyclic
        from ..models.cholesky import tiled_cholesky_ptg
        n, nb = n or 8192, nb or 512
        A = SymTwoDimBlockCyclic("A", n, n, nb, nb, init_fn=zeros())
        return tiled_cholesky_ptg(A), dict(n=n, nb=nb)
    if workload == "lu":
        from ..data_dist.matrix import TiledMatrix
        from ..models.lu import tiled_lu_ptg
        n, nb = n or 8192, nb or 512
        A = TiledMatrix("A", n, n, nb, nb, dtype=np.float32,
                        init_fn=zeros())
        return tiled_lu_ptg(A), dict(n=n, nb=nb)
    if workload == "stencil":
        from ..data_dist.matrix import VectorTwoDimCyclic
        from ..models.stencil import stencil_1d_ptg
        n, mb = n or (1 << 24), nb or (1 << 18)
        V = VectorTwoDimCyclic("V", lm=n, mb=mb, P=1,
                               init_fn=lambda m, size:
                               np.zeros(size, np.float32))
        w = np.full(9, 1.0 / 9.0)
        return stencil_1d_ptg(V, w, 64), dict(n=n, mb=mb)
    if workload == "llm_decode":
        from ..data.datatype import TileType
        from ..data_dist.collection import DictCollection
        from ..data_dist.paged_kv import PagedKVCollection
        from ..llm.decode import decode_step_ptg
        nseqs, npages = n or 8, nb or 4
        kv = PagedKVCollection("KV", page_size=16)
        H, D = kv.num_heads, kv.head_dim
        Q = DictCollection("Q", dtt=TileType((3, H, D), np.float32))
        O = DictCollection("O", dtt=TileType((H, D), np.float32))
        seqs = [f"s{i}" for i in range(nseqs)]
        for s in seqs:
            kv.alloc_seq(s)
            for _ in range(npages):
                kv.alloc_page(s)
            kv.note_appended(s, npages * kv.page_size - 1)
            kv.ensure_tail_slot(s)
        tp = decode_step_ptg(kv, Q, O, seqs, devices="auto")
        return tp, dict(nseqs=nseqs, npages=npages)
    if workload == "llm_decode_k":
        # the k-step decode superpool (ISSUE 9): n = sequences, nb =
        # steps per pool — warming it AOT is what keeps the serving
        # path's region-lowered incarnation (llm_lower_regions) from
        # paying XLA at first-token time
        from ..data.datatype import TileType
        from ..data_dist.collection import DictCollection
        from ..data_dist.paged_kv import PagedKVCollection
        from ..llm.decode import (decode_superpool_ptg,
                                  preallocate_decode_steps)
        from ..llm.model import ToyLM
        nseqs, ksteps = n or 8, nb or 8
        model = ToyLM()
        kv = PagedKVCollection("KV", page_size=16,
                               num_heads=model.num_heads,
                               head_dim=model.head_dim)
        H, D = kv.num_heads, kv.head_dim
        Q = DictCollection("Q", dtt=TileType((3, H, D), np.float32))
        O = DictCollection("O", dtt=TileType((H, D), np.float32))
        TOK = DictCollection("TOK", dtt=TileType((3,), np.float32))
        EMB = DictCollection("EMB", dtt=TileType(
            model.q3_table().shape, np.float32))
        seqs = [f"s{i}" for i in range(nseqs)]
        for s in seqs:
            kv.alloc_seq(s)
            for _ in range(3):
                kv.alloc_page(s)
            kv.note_appended(s, 3 * kv.page_size - 1)
            preallocate_decode_steps(kv, s, ksteps)
            TOK.data_of(s, -1)          # materialize the chain seed
        tp = decode_superpool_ptg(kv, Q, O, TOK, EMB, seqs,
                                  [ksteps] * nseqs, devices="auto")
        return tp, dict(nseqs=nseqs, steps=ksteps)
    if workload == "llm_spec_k":
        # the batched speculative superpool (ISSUE 12): n = sequences,
        # nb = draft tokens per stream (1 + nb positions, the serving
        # path's pad) — warming it AOT keeps the spec serving path
        # (llm_spec_k > 0) from paying cold XLA at first-draft time in
        # bench/tier-1
        from ..data.datatype import TileType
        from ..data_dist.collection import DictCollection
        from ..data_dist.paged_kv import PagedKVCollection
        from ..llm.decode import (preallocate_decode_steps,
                                  seed_spec_batched, spec_batched_ptg)
        from ..llm.model import ToyLM
        nseqs, kdraft = n or 8, nb or 8
        model = ToyLM()
        kv = PagedKVCollection("KV", page_size=16,
                               num_heads=model.num_heads,
                               head_dim=model.head_dim)
        H, D = kv.num_heads, kv.head_dim
        QS = DictCollection("QS", dtt=TileType((kdraft + 1, 3, H, D),
                                               np.float32))
        LIM = DictCollection("LIM", dtt=TileType((kdraft + 1,),
                                                 np.float32))
        DTOKS = DictCollection("DTOKS", dtt=TileType((kdraft + 3,),
                                                     np.float32))
        VOUT = DictCollection("VOUT", dtt=TileType((kdraft + 3,),
                                                   np.float32))
        EMB = DictCollection("EMB", dtt=TileType(
            model.q3_table().shape, np.float32))
        seqs = [f"s{i}" for i in range(nseqs)]
        for s in seqs:
            kv.alloc_seq(s)
            for _ in range(3):
                kv.alloc_page(s)
            kv.note_appended(s, 3 * kv.page_size - 1)
            preallocate_decode_steps(kv, s, kdraft + 1)
            seed_spec_batched(model, kv, QS, LIM, DTOKS, s, 0,
                              list(range(1, kdraft + 1)), kdraft + 1)
        tp = spec_batched_ptg(kv, QS, LIM, DTOKS, VOUT, EMB, seqs,
                              [kdraft + 1] * nseqs, pad=kdraft + 1,
                              devices="auto")
        return tp, dict(nseqs=nseqs, draft=kdraft)
    if workload == "llm_prefill_tail":
        # the prefix-cache admission shape (ISSUE 11): streams whose
        # prompt matched the radix trie prefill only their unmatched
        # tail (prefill_ptg(starts=)), so the hot serving path compiles
        # THIS pool geometry — warming it keeps trie-hit prefills from
        # paying cold XLA at admission time.  n = sequences, nb = tail
        # pages per sequence (on top of a fixed 4-page shared prefix).
        from ..data.datatype import TileType
        from ..data_dist.collection import DictCollection
        from ..data_dist.paged_kv import PagedKVCollection
        from ..llm.decode import prefill_ptg
        nseqs, tail_pages = n or 8, nb or 2
        prefix_pages = 4
        kv = PagedKVCollection("KV", page_size=16)
        seqs = [f"s{i}" for i in range(nseqs)]
        tkeys = []
        for s in seqs:
            kv.alloc_seq(s)
            for _ in range(prefix_pages + tail_pages):
                kv.alloc_page(s)
            kv.note_appended(s, (prefix_pages + tail_pages)
                             * kv.page_size)
            tkeys += [(s, c) for c in range(prefix_pages,
                                            prefix_pages + tail_pages)]
        T = DictCollection("T", dtt=kv.default_dtt, keys=tkeys,
                           init_fn=lambda *k:
                           np.zeros(kv.default_dtt.shape, np.float32))
        tp = prefill_ptg(kv, T, seqs, devices="auto",
                         starts=[prefix_pages] * nseqs)
        return tp, dict(nseqs=nseqs, tail_pages=tail_pages)
    raise ValueError(f"unknown warm workload {workload!r} (gemm, "
                     f"cholesky, lu, stencil, llm_decode, llm_decode_k, "
                     f"llm_spec_k, llm_prefill_tail)")


def warm_cache(workload: str, n: int | None = None, nb: int | None = None,
               modes: tuple = ("auto", "region"),
               budget_s: float | None = None) -> dict:
    """Populate the persistent lowering/compile caches for one workload
    ahead of a bench run (so no stage dies compiling inside its
    deadline): every requested mode traces + compiles AOT against
    abstract avals, landing executables in JAX's persistent compilation
    cache — a later process at the same geometry pays deserialization,
    not XLA.  Returns per-mode timings."""
    tp, geom = _warm_workload(workload, n, nb)
    out: dict = {"workload": workload, **geom,
                 "backend": list(_backend_signature())}
    for mode in modes:
        t0 = time.perf_counter()
        try:
            if mode == "region":
                plan = lower_regions(tp)
                st = plan.compile(budget_s=budget_s)
                out["region"] = {k: st[k] for k in
                                 ("regions", "regions_compiled",
                                  "regions_eager", "trace_s", "compile_s")}
            else:
                low = lower_taskpool(tp, passes=mode)
                out[mode] = {"mode": low.mode, **low.warm()}
        except LoweringError as e:
            out[mode] = {"error": str(e)}
        out.setdefault("wall_s", {})[mode] = round(
            time.perf_counter() - t0, 3)
    return out


def _main(argv: list[str] | None = None) -> int:
    """``python -m parsec_tpu.ptg.lowering --warm <workload> [--n --nb]``
    — the AOT cache-warming CLI (scripts/warm_cache.sh wraps it)."""
    import argparse
    import json
    ap = argparse.ArgumentParser(
        prog="python -m parsec_tpu.ptg.lowering",
        description="AOT lowering/compile cache warmer: compile a "
                    "workload's lowered programs into the persistent "
                    "compilation cache before a bench run's stage clock "
                    "starts (docs/PERF.md, 'Region lowering & compile "
                    "budgets').")
    ap.add_argument("--warm", metavar="WORKLOAD", required=True,
                    help="gemm | cholesky | lu | stencil | llm_decode | "
                         "llm_decode_k | llm_spec_k | llm_prefill_tail")
    ap.add_argument("--n", type=int, default=None,
                    help="problem size (stencil: vector length; "
                    "llm_decode/llm_decode_k/llm_spec_k/"
                    "llm_prefill_tail: sequence count)")
    ap.add_argument("--nb", type=int, default=None,
                    help="tile size (stencil: segment size; llm_decode: "
                    "pages per sequence; llm_decode_k: steps per "
                    "superpool; llm_spec_k: draft tokens per stream; "
                    "llm_prefill_tail: tail pages)")
    ap.add_argument("--nt", type=int, default=None,
                    help="tile count (alternative to --n: n = nt * nb)")
    ap.add_argument("--modes", default="auto,region",
                    help="comma list of lowering modes to warm "
                    "(auto, wavefront, unrolled, chain-collapse, region)")
    ap.add_argument("--budget", type=float, default=None,
                    help="compile budget seconds for the region mode "
                    "(default: the lowering_compile_budget_s MCA param)")
    args = ap.parse_args(argv)
    n = args.n
    if n is None and args.nt is not None:
        n = args.nt * (args.nb or 512)
    out = warm_cache(args.warm, n=n, nb=args.nb,
                     modes=tuple(m.strip() for m in args.modes.split(",")
                                 if m.strip()),
                     budget_s=args.budget)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    # under `python -m` runpy executes a FRESH module copy whose
    # traceable registry the model modules never see — delegate to the
    # canonical module object so registration and lookup share state
    from parsec_tpu.ptg.lowering import _main as _canonical_main
    raise SystemExit(_canonical_main())
