"""Task classes, flows, dependencies, task instances.

Rebuild of the reference's task model (``parsec_internal.h``): a *task class*
(``parsec_task_class_t``, :409-457) describes one kind of micro-task — its
parameters ("locals"), dataflow (flows with guarded in/out deps), data
affinity, priority, and a list of *incarnations* ("chores") binding bodies to
device types; a *task* (:539-551) is one instance with concrete locals.

TPU-first notes: a chore's body is a host callable for CPU incarnations and a
kernel-registry name (compiled XLA/Pallas executable) for TPU incarnations;
``time_estimate`` feeds best-device selection exactly as in the reference
(``parsec_internal.h:441``).
"""

from __future__ import annotations

import itertools
from typing import Any, Callable, Sequence


# Hook return protocol (cf. runtime.h:139-147).
HOOK_RETURN_DONE = 0        # body executed to completion
HOOK_RETURN_ASYNC = -1      # body progresses asynchronously (device owns it)
HOOK_RETURN_AGAIN = -2      # reschedule the same chore later
HOOK_RETURN_NEXT = -3       # try the next chore / device
HOOK_RETURN_DISABLE = -4    # disable this chore for every task of the class
HOOK_RETURN_ERROR = -5

# Flow kinds: data access modes come from parsec_tpu.data; CTL is pure control.
FLOW_CTL = "CTL"

# Device type tags for chores (cf. PARSEC_DEV_* masks).
DEV_CPU = "cpu"
DEV_TPU = "tpu"
DEV_RECURSIVE = "recursive"

_task_counter = itertools.count()

_UNSET = object()   # lazy-attribute sentinel (space_extents)

# bits of a task's IN-dep mask the native dep table holds (``uint64_t``,
# native/src/core.cpp): a class with more input deps is tracked by count
MASK_BITS = 64


class Dep:
    """One dependency edge endpoint on a flow (cf. ``parsec_dep_t``).

    For an *output* dep: when ``guard(locals)`` holds, the flow's datum feeds
    task ``target_class`` instance ``target_params(locals)`` on flow
    ``target_flow``; ``target_class is None`` means the edge writes back to
    the data collection (``A(k)`` arrow target).  For an *input* dep the
    fields describe the predecessor symmetrically; ``target_class is None``
    means the flow reads directly from the collection.

    With all targets None the dep is a *NEW* arrow (the flow allocates a
    fresh tile of its declared type when this dep is active) or, with
    ``null=True``, a *NULL* arrow (the flow explicitly carries no data) —
    the JDF ``<- NEW`` / ``<- NULL`` endpoints (``jdf.h`` JDF_VAR special
    cases).
    """

    __slots__ = ("guard", "target_class", "target_flow", "target_params",
                 "dtt", "data_ref", "null", "ranged", "wire")

    def __init__(self, guard: Callable[[dict], bool] | None = None,
                 target_class: str | None = None,
                 target_flow: str | None = None,
                 target_params: Callable[[dict], tuple] | None = None,
                 dtt: Any = None,
                 data_ref: Callable[[dict], tuple] | None = None,
                 null: bool = False, ranged: bool = False,
                 wire: Any = None) -> None:
        self.guard = guard
        self.target_class = target_class
        self.target_flow = target_flow
        self.target_params = target_params
        self.dtt = dtt
        self.data_ref = data_ref  # (collection, key...) accessor for dc edges
        self.null = null
        # ranged INPUT dep (JDF `<- ctl T(k, 0 .. NB .. 2)`): one declared
        # dep expecting len(each_target) arrivals — the class switches from
        # mask to goal-counted dep tracking (dependencies_goal protocol)
        self.ranged = ranged
        # partial-tile wire datatype (the JDF [type_remote/displ_remote]
        # pair): a tuple of slices, or callable(locals) -> slices, naming
        # the sub-view of the tile a REMOTE edge ships; local edges ignore
        # it (data/datatype.py WireRegion)
        self.wire = wire

    def wire_slices(self, locals_: dict) -> tuple | None:
        if self.wire is None:
            return None
        return self.wire(locals_) if callable(self.wire) else self.wire

    def active(self, locals_: dict) -> bool:
        return self.guard is None or bool(self.guard(locals_))

    def flow_name(self, locals_: dict) -> str | None:
        """The flow at the other end: ``target_flow``, or what it gives for
        ``locals_`` where it is a function (a class whose flows form a
        family, one a tile row: ``T3`` of ``PANEL`` for ``GEMM(3, n, k)``).
        An output dep's function reads the producer's locals, an input
        dep's the consumer's."""
        tf = self.target_flow
        return tf(locals_) if callable(tf) else tf

    def each_target(self, locals_: dict) -> tuple[dict, ...]:
        """Successor instances of this out-dep for ``locals_``.

        ``target_params`` may return one locals dict or a sequence of them —
        the JDF *range arrow* form (``-> T TRSM(k+1..NT-1, k)``), one edge
        fanning out to many instances.  Input deps are always single-target.
        """
        t = self.target_params(locals_)
        if isinstance(t, dict):
            return (t,)
        return tuple(t)


class Flow:
    """A named dataflow of a task class (cf. ``parsec_flow_t``)."""

    __slots__ = ("name", "access", "flow_index", "deps_in", "deps_out", "dtt")

    def __init__(self, name: str, access: Any, flow_index: int = -1,
                 deps_in: Sequence[Dep] = (), deps_out: Sequence[Dep] = (),
                 dtt: Any = None) -> None:
        self.name = name
        self.access = access            # ACCESS_* or FLOW_CTL
        self.flow_index = flow_index
        self.deps_in = list(deps_in)
        self.deps_out = list(deps_out)
        self.dtt = dtt                  # TileType for scratch allocation

    @property
    def is_ctl(self) -> bool:
        return self.access == FLOW_CTL


class Chore:
    """One incarnation of a task class on a device type (cf. ``__parsec_chore_t``)."""

    __slots__ = ("device_type", "hook", "evaluate", "dyld", "enabled")

    def __init__(self, device_type: str, hook: Callable | None = None,
                 evaluate: Callable | None = None, dyld: str | None = None) -> None:
        self.device_type = device_type
        self.hook = hook          # (es, task) -> HOOK_RETURN_*
        self.evaluate = evaluate  # (es, task) -> DONE (use) / NEXT (skip)
        self.dyld = dyld          # kernel-registry name for device bodies
        self.enabled = True


class KeyHashStruct:
    """User-defined key semantics (cf. ``parsec_key_fn_t`` and the JDF
    ``hash_struct`` property, ``jdf.h:189-190``): ``key_hash(key) -> int``,
    ``key_equal(a, b) -> bool``, ``key_print(key) -> str``.  Installed on a
    task class it governs how that class's task keys hash/compare in the
    dep-tracking and repo hash tables (via :class:`UDKey`)."""

    __slots__ = ("key_hash", "key_equal", "key_print")

    def __init__(self, key_hash: Callable[[Any], int] | None = None,
                 key_equal: Callable[[Any, Any], bool] | None = None,
                 key_print: Callable[[Any], str] | None = None) -> None:
        self.key_hash = key_hash
        self.key_equal = key_equal
        self.key_print = key_print


class UDKey:
    """A task key carrying a :class:`KeyHashStruct`: Python hash tables
    (the tracker/repo stores) call straight into the user's hash/equal."""

    __slots__ = ("key", "hs")

    def __init__(self, key: tuple, hs: KeyHashStruct) -> None:
        self.key = key
        self.hs = hs

    def __hash__(self) -> int:
        if self.hs.key_hash is not None:
            return int(self.hs.key_hash(self.key))
        return hash(self.key)

    def __eq__(self, other: Any) -> bool:
        ok = other.key if isinstance(other, UDKey) else other
        if self.hs.key_equal is not None:
            return bool(self.hs.key_equal(self.key, ok))
        return self.key == ok

    def __repr__(self) -> str:
        if self.hs.key_print is not None:
            return self.hs.key_print(self.key)
        return repr(self.key)


class TaskClass:
    """Static description of one task kind (cf. ``parsec_task_class_t``)."""

    def __init__(self, name: str, params: Sequence[str],
                 flows: Sequence[Flow], chores: Sequence[Chore],
                 task_class_id: int = -1,
                 affinity: Callable[[dict], tuple] | None = None,
                 priority: Callable[[dict], int] | None = None,
                 time_estimate: Callable[[Any, Any], float] | None = None,
                 prepare_input: Callable | None = None,
                 complete_execution: Callable | None = None,
                 make_key_fn: Callable[[dict], Any] | None = None,
                 find_deps_fn: Callable | None = None,
                 hash_struct: Any = None,
                 startup_fn: Callable | None = None,
                 simcost: Callable[[dict], float] | None = None) -> None:
        self.name = name
        self.params = list(params)
        self.flows = list(flows)
        for i, f in enumerate(self.flows):
            f.flow_index = i
        self.chores = list(chores)
        self.task_class_id = task_class_id
        self.affinity = affinity          # locals -> (collection, key) rank home
        self.priority = priority
        self.time_estimate = time_estimate
        self.prepare_input = prepare_input
        self.complete_execution = complete_execution
        # user-defined overrides (jdf.h:185-210): custom key construction,
        # custom dep-storage location, custom key hashing, custom startup
        # enumeration, and the PARSEC_SIM cost model (parsec.y:635-641)
        self.make_key_fn = make_key_fn
        self.find_deps_fn = find_deps_fn
        self.hash_struct = hash_struct    # KeyHashStruct or None
        self.startup_fn = startup_fn
        self.simcost = simcost
        # execution-space membership test (locals -> bool), set by space-
        # aware front-ends: out-of-space successor edges are DROPPED at
        # release like the reference's generated bounds checks — C-syntax
        # JDFs lean on this (`(k < NT) ? T PING(k+1)` at k = NT-1)
        self.in_space: Callable[[dict], bool] | None = None
        # what this class's locals-taking callables (guards, in_space,
        # priority, target params, data refs) read an instance's locals
        # through, when it is something a caller can build once per
        # instance and hand to each of them in place of the dict: the PTG
        # front-end sets its namespace constructor here.  None: the dict.
        self.locals_view: Callable[[dict], Any] | None = None
        # the release plan of this class's out-deps (runtime/scheduling.py:
        # _plan_release), built at the class's first release in its pool
        self._release_plan: Any = None
        # (dependency tracker, whether it keeps this class's trackers in
        # its dense index-array tier): DependencyTracking._indexed_eligible
        self._indexed_memo: Any = None
        # static execution-space box ((lo, stop) per param) when every
        # range is locals-independent with unit step — enables the
        # index-array dep-storage variant (parsec_default_find_deps,
        # parsec.c:1479 / ptg-compiler `-M index-array`).  Resolved
        # LAZILY at first use through space_extents_fn so globals bound
        # after build() are honored, matching in_space's first-use
        # capture of the same static ranges.
        self.space_extents_fn: Callable[[], tuple | None] | None = None
        self._space_extents: Any = _UNSET
        self.repo = None                  # DataRepo, attached by the taskpool
        self.dependencies_goal = 0        # static goal unused when guarded
        # a device batch may append zero tiles to the trailing data flows
        # (``pad_rows``: (lead, bucket), set by the front end; None: never)
        self.pad_rows: tuple[int, int] | None = None
        # the most instances a device batch holds (``batch_max``, set by the
        # front end; None: the device's own bound)
        self.batch_max: int | None = None
        # make_key on the C path: itemgetter over the param names
        from operator import itemgetter
        if len(self.params) >= 2:
            self._keyget = itemgetter(*self.params)
        elif len(self.params) == 1:
            g = itemgetter(self.params[0])
            self._keyget = lambda d: (g(d),)
        else:
            self._keyget = lambda d: ()
        # precomputed (flow_index, dep_index) -> bit position (hot path),
        # and the input deps a predecessor task feeds with the mask bit of
        # each: all that input_dep_mask has to look at
        self._dep_bits: dict[tuple[int, int], int] = {}
        self._pred_in: list[tuple[int, Dep]] = []
        bit = 0
        for fi, f in enumerate(self.flows):
            for di, d in enumerate(f.deps_in):
                self._dep_bits[(fi, di)] = bit
                if d.target_class is not None:
                    self._pred_in.append((1 << bit, d))
                bit += 1
        # counted mode: arrivals are *counted* toward a per-task goal
        # instead of OR-ed into a bitmask (the reference's
        # dependencies_goal counting vs mask protocol) where a ranged input
        # dep fans N arrivals into one declared dep, and where the input
        # deps outnumber the native table's mask (a class with a flow a
        # tile row: every flow still has one active input, so no datum
        # slot is raced)
        self.counted = bit > MASK_BITS or any(
            d.ranged for f in self.flows for d in f.deps_in)

    # -- keys ---------------------------------------------------------------
    def make_key(self, locals_: dict) -> tuple:
        """Canonical task key (cf. generated ``make_key`` fns).

        A user ``make_key_fn`` (``JDF_PROP_UD_MAKE_KEY_FN_NAME``) replaces
        the positional-params key; non-tuple results are wrapped so every
        consumer still sees a tuple.  A ``hash_struct`` additionally wraps
        the key so user ``key_hash``/``key_equal`` drive the hash tables."""
        if self.make_key_fn is not None:
            k = self.make_key_fn(locals_)
            k = k if isinstance(k, tuple) else (k,)
        else:
            k = self._keyget(locals_)
        if self.hash_struct is not None:
            return (UDKey(k, self.hash_struct),)
        return k

    def view_of(self, locals_: dict) -> Any:
        """What this class's locals-taking callables read ``locals_``
        through (``locals_view``): built once by a caller that evaluates
        several of them for one instance."""
        view = self.locals_view
        return locals_ if view is None else view(locals_)

    # -- dep structure ------------------------------------------------------
    @property
    def space_extents(self) -> tuple | None:
        if self._space_extents is _UNSET:
            fn = self.space_extents_fn
            self._space_extents = fn() if fn is not None else None
        return self._space_extents

    def input_dep_mask(self, locals_: dict) -> int:
        """Bitmask of (flow_index, dep_index) input deps active for these
        locals — the per-task IN-dep mask (cf. ``parsec.c:1293``)."""
        mask = 0
        for bit, d in self._pred_in:
            g = d.guard
            if g is None or g(locals_):
                # an active ranged dep whose range is EMPTY for these
                # locals expects zero arrivals: it must not gate
                # readiness (keeps the mask consistent with
                # input_dep_goal — the dependencies_goal protocol)
                if not d.ranged or d.each_target(locals_):
                    mask |= bit
        return mask

    def input_dep_goal(self, locals_: dict) -> int:
        """Expected input-arrival count for counted classes: each active
        task-predecessor dep contributes one arrival per target instance
        (ranged deps fan in len(each_target) arrivals)."""
        goal = 0
        for f in self.flows:
            for d in f.deps_in:
                if d.target_class is None or not d.active(locals_):
                    continue
                goal += len(d.each_target(locals_)) if d.ranged else 1
        return goal

    def dep_bit(self, flow_index: int, dep_index: int) -> int:
        try:
            return self._dep_bits[(flow_index, dep_index)]
        except KeyError:
            raise IndexError((flow_index, dep_index))

    def iterate_successors(self, task: "Task", visitor: Callable) -> None:
        """Visit every *active* out-dep edge of ``task``.

        ``visitor(task, flow, dep)`` — the analog of the generated
        ``iterate_successors`` walking guarded arrow targets inline
        (SURVEY §3.3).
        """
        for f in self.flows:
            for d in f.deps_out:
                if d.active(task.locals):
                    visitor(task, f, d)

    def __repr__(self) -> str:
        return f"<TaskClass {self.name}({', '.join(self.params)})>"


class Task:
    """One executable instance of a task class (cf. ``parsec_task_t``)."""

    __slots__ = ("taskpool", "task_class", "locals", "priority", "data",
                 "repo_entries", "status", "chore_mask", "uid",
                 "selected_device", "_mempool_owner", "on_complete",
                 "sim_exec_date", "__weakref__")

    def __init__(self, taskpool: Any, task_class: TaskClass,
                 locals_: dict, priority: int = 0) -> None:
        self.taskpool = taskpool
        self.task_class = task_class
        self.locals = locals_
        self.priority = priority
        # per-flow resolved input copies; outputs written here too
        self.data: list[Any] = [None] * len(task_class.flows)
        # per-flow (repo_entry, src_flow_index) to consume after execution
        self.repo_entries: list[Any] = [None] * len(task_class.flows)
        self.status = "nascent"
        self.chore_mask = (1 << len(task_class.chores)) - 1
        self.uid = next(_task_counter)
        self.selected_device = None
        self.on_complete = None
        self.sim_exec_date = 0.0   # PARSEC_SIM simulated completion date

    @property
    def key(self) -> tuple:
        return self.task_class.make_key(self.locals)

    def flow_data(self, name: str) -> Any:
        for f in self.task_class.flows:
            if f.name == name:
                return self.data[f.flow_index]
        raise KeyError(name)

    def set_flow_data(self, name: str, value: Any) -> None:
        for f in self.task_class.flows:
            if f.name == name:
                self.data[f.flow_index] = value
                return
        raise KeyError(name)

    def __repr__(self) -> str:
        args = ", ".join(f"{p}={self.locals[p]}" for p in self.task_class.params)
        return f"<Task {self.task_class.name}({args})>"
