"""PTG: the Parameterized Task Graph DSL, algebraic builder form.

Rebuild of the reference's JDF front-end (SURVEY §2.7) as a Python-embedded
algebraic API instead of a flex/bison→C compiler: a taskpool is described
problem-size-independently by task classes with

- *parameters* spanning an execution space (range expressions that may depend
  on globals and on previously-bound parameters — triangular spaces work),
- a *data affinity* (``: A(k)``) fixing the owning rank,
- *flows* (``RW``/``READ``/``WRITE``/``CTL``) with guarded input/output
  dependency arrows to other task classes or to the collection,
- per-device *bodies* (chores), and an optional priority expression.

The builder materializes :class:`~parsec_tpu.runtime.task.TaskClass` objects
and a :class:`PTGTaskpool` whose startup enumerates the execution space and
schedules the tasks whose IN-dep masks are empty (the generated
``startup``/``internal_init`` contract, ``jdf2c.c:3035``/``:3431``).  The JDF
*textual* front-end (:mod:`parsec_tpu.ptg.jdf`) parses into this same builder,
so both front-ends share one backend — mirroring ``parsec_ptgpp`` emitting
code against one runtime ABI.

Guard/range/assignment expressions are callables ``fn(g, l)`` receiving
read-only namespaces of globals and locals; the JDF parser compiles its
expression strings into exactly these.
"""

from __future__ import annotations

from types import SimpleNamespace
from typing import Any, Callable, Iterable

from ..data.data import ACCESS_READ, ACCESS_RW, ACCESS_WRITE
from ..runtime.task import (FLOW_CTL, HOOK_RETURN_DONE, Chore, Dep, Flow,
                            TaskClass)
from ..runtime.taskpool import Taskpool

READ = ACCESS_READ
WRITE = ACCESS_WRITE
RW = ACCESS_RW
CTL = FLOW_CTL


class _NS(SimpleNamespace):
    def __getitem__(self, k):
        return getattr(self, k)


def _ns(d: Any) -> _NS:
    """The namespace a ``fn(g, l)`` expression reads its locals from.  A
    caller that evaluates several expressions of ONE task instance (the
    release path: guards, ranges, keys, priority) builds it once through the
    class's ``locals_view`` and hands the namespace itself to each wrapper,
    which passes it through here untouched."""
    return d if d.__class__ is _NS else _NS(**d)


class _DictNS:
    """Live attribute view over a dict (globals namespace, hot path: built
    once per builder; later mutations of the dict are visible)."""

    __slots__ = ("_d",)

    def __init__(self, d: dict) -> None:
        object.__setattr__(self, "_d", d)

    def __getattr__(self, k):
        try:
            return self._d[k]
        except KeyError:
            raise AttributeError(k)

    def __getitem__(self, k):
        return self._d[k]

    @property
    def __dict__(self):   # vars(g) support (the JDF expression evaluator)
        return self._d


class FlowBuilder:
    def __init__(self, tcb: "TaskClassBuilder", name: str, access: Any,
                 dtt: Any = None) -> None:
        self._tcb = tcb
        self.name = name
        self.access = access
        self.dtt = dtt
        self._deps_in: list[Dep] = []
        self._deps_out: list[Dep] = []

    def input(self, pred: tuple | None = None, data: tuple | None = None,
              guard: Callable | None = None, dtt: Any = None,
              new: bool = False, null: bool = False,
              ranged: bool = False) -> "FlowBuilder":
        """Add an input arrow.

        ``pred=(class_name, flow_name, params_fn)`` for a task predecessor;
        ``data=(collection_or_name, key_fn)`` for a direct collection read;
        ``new=True`` for a fresh-tile allocation (JDF ``<- NEW``; the flow
        needs a declared tile type); ``null=True`` for an explicit no-data
        input (JDF ``<- NULL``).  ``params_fn(g, l) -> dict`` binds the
        predecessor's locals; ``key_fn(g, l) -> tuple`` the collection key.
        ``ranged=True`` marks a *fan-in* arrow whose ``params_fn`` returns a
        sequence of predecessor instances, each expected to arrive (the JDF
        range-input form ``<- ctl T(k, 0 .. NB .. 2)``; CTL joins)."""
        if new and dtt is None and self.dtt is None:
            raise ValueError(
                f"flow {self.name}: NEW needs a tile type to allocate "
                f"(pass dtt= on the arrow or declare it on the flow)")
        if ranged and self.access != CTL:
            # N producers racing one datum slot is nondeterministic — the
            # counted fan-in protocol is for control joins only (both
            # front-ends inherit this check)
            raise ValueError(
                f"flow {self.name}: ranged fan-in input on a data flow; "
                f"range inputs are CTL-only")
        self._deps_in.append(self._tcb._mk_dep(pred, data, guard, dtt,
                                               new=new, null=null,
                                               ranged=ranged))
        if new and dtt is not None and self.dtt is None:
            self.dtt = dtt      # NEW allocates at the flow's declared type
        return self

    def output(self, succ: tuple | None = None, data: tuple | None = None,
               guard: Callable | None = None, dtt: Any = None,
               wire: Any = None) -> "FlowBuilder":
        """``wire=`` tags the edge with a partial-tile wire datatype
        (JDF ``[type_remote = .., displ_remote = ..]``): a tuple of
        slices or ``wire_fn(g, l) -> slices`` selecting the sub-view a
        REMOTE consumer receives; same-rank edges always share the full
        tile (see data/datatype.py WireRegion)."""
        self._deps_out.append(self._tcb._mk_dep(succ, data, guard, dtt,
                                                wire=wire))
        return self

    def _build(self) -> Flow:
        return Flow(self.name, self.access, deps_in=self._deps_in,
                    deps_out=self._deps_out, dtt=self.dtt)


class TaskClassBuilder:
    def __init__(self, ptg: "PTGBuilder", name: str,
                 params: dict[str, Callable]) -> None:
        self._ptg = ptg
        self.name = name
        # param name -> fn(g, l) -> iterable (l holds previously-bound params)
        self.param_ranges = dict(params)
        self._flows: list[FlowBuilder] = []
        self._chores: list[Chore] = []
        self._affinity: Callable | None = None
        self._priority: Callable | None = None
        self._time_estimate: Callable | None = None
        # user-defined overrides (jdf.h:185-210) + SIMCOST (parsec.y:635)
        self._make_key: Callable | None = None
        self._find_deps: Callable | None = None
        self._hash_struct: Any = None
        self._startup: Callable | None = None
        self._simcost: Callable | None = None
        self._stage_in_hook: Callable | None = None
        self._stage_out_hook: Callable | None = None
        self._pad_rows: tuple[int, int] | None = None
        self._batch_max: int | None = None

    # -- structure ----------------------------------------------------------
    def affinity(self, collection: Any, key_fn: Callable) -> "TaskClassBuilder":
        dc_get = self._ptg._dc_getter(collection)

        g = self._ptg._g_view

        def aff(locals_: dict) -> tuple:
            return dc_get(), key_fn(g, _ns(locals_))

        self._affinity = aff
        return self

    def flow(self, name: str, access: Any, dtt: Any = None) -> FlowBuilder:
        fb = FlowBuilder(self, name, access, dtt)
        self._flows.append(fb)
        return fb

    def priority(self, fn: Callable) -> "TaskClassBuilder":
        g = self._ptg._g_view
        self._priority = lambda locals_: int(fn(g, _ns(locals_)))
        return self

    def time_estimate(self, fn: Callable) -> "TaskClassBuilder":
        self._time_estimate = fn
        return self

    def pad_rows(self, lead: int, bucket: int) -> "TaskClassBuilder":
        """The class's data flows after its first ``lead`` form a family
        (a tile row each) of which an instance leaves some ``null``: a
        device batch hands its kernel the family's present tiles followed
        by tiles of zeros, up to a multiple of ``bucket``, so that the
        instances of every height between two multiples share one program.
        The kernel has to leave a zero tile zero; what it returns for one
        goes back to the device's pool of zeros."""
        self._pad_rows = (int(lead), int(bucket))
        return self

    def batch_max(self, lanes: int) -> "TaskClassBuilder":
        """A device batch of the class holds at most ``lanes`` instances
        (below ``device_tpu_batch_max``): each lane of a fused batch program
        is code of its own, and every lane count up to the largest batch is
        a program that the compile cache has to hold."""
        self._batch_max = int(lanes)
        return self

    # -- user-defined overrides (the jdf.h:185-210 UD property family) ------
    def make_key(self, fn: Callable) -> "TaskClassBuilder":
        """``make_key_fn``: custom task-key construction, ``fn(g, l) -> key``
        (any hashable; non-tuples are wrapped by the runtime)."""
        g = self._ptg._g_view
        self._make_key = lambda locals_: fn(g, _ns(locals_))
        return self

    def find_deps(self, fn: Callable) -> "TaskClassBuilder":
        """``find_deps_fn``: custom dep-storage location,
        ``fn(taskpool, g, l) -> hashable identity``."""
        g = self._ptg._g_view
        self._find_deps = lambda tp, locals_: fn(tp, g, _ns(locals_))
        return self

    def hash_struct(self, key_hash: Callable | None = None,
                    key_equal: Callable | None = None,
                    key_print: Callable | None = None) -> "TaskClassBuilder":
        """``hash_struct``: user key hashing/equality/printing over the raw
        key tuples (``parsec_key_fn_t`` analog)."""
        from ..runtime.task import KeyHashStruct
        self._hash_struct = KeyHashStruct(key_hash, key_equal, key_print)
        return self

    def startup(self, fn: Callable) -> "TaskClassBuilder":
        """``startup_fn``: custom startup enumeration for this class,
        ``fn(taskpool, context, g) -> iterable of locals dicts`` naming the
        initially-ready instances (replacing the empty-IN-mask scan)."""
        self._startup = fn
        return self

    def simcost(self, fn: Callable) -> "TaskClassBuilder":
        """``SIMCOST``: simulated execution cost ``fn(g, l) -> float``; the
        pool then tracks ``largest_simulation_date`` (PARSEC_SIM model)."""
        g = self._ptg._g_view
        self._simcost = lambda locals_: fn(g, _ns(locals_))
        return self

    def stage_hooks(self, stage_in: Callable | None = None,
                    stage_out: Callable | None = None
                    ) -> "TaskClassBuilder":
        """User transfer hooks for this class's device tasks
        (``stage_custom.jdf`` role, ``device_gpu.h:61-77``): each is
        ``fn(device, task)`` replacing the default versioned stage-in /
        stage-out around the device dispatch.  Only the arguments given
        are updated — separate calls may set the two hooks."""
        if stage_in is not None:
            self._stage_in_hook = stage_in
        if stage_out is not None:
            self._stage_out_hook = stage_out
        return self

    def body(self, fn: Callable | None = None, device: str = "cpu",
             dyld: str | None = None,
             evaluate: Callable | None = None) -> Any:
        """Attach a body for ``device`` (multiple BODY...END analog).

        CPU bodies are callables ``fn(es, task, g, l)``; device bodies may
        instead name a kernel-registry entry via ``dyld`` (the JDF ``dyld=``
        incarnation contract).  Usable as a decorator: ``@tc.body``.
        """
        def attach(f: Callable | None) -> Callable | None:
            if device in ("cpu", "recursive"):
                # recursive incarnations are host callables too: the body
                # spawns a nested taskpool via runtime.recursive_call and
                # returns its ASYNC (PARSEC_DEV_RECURSIVE, device.h:64)
                hook = self._wrap_cpu_body(f)
            else:
                from ..device.hooks import make_device_hook
                hook = make_device_hook(device, f, dyld, self._ptg)
            self._chores.append(Chore(device, hook=hook, evaluate=evaluate,
                                      dyld=dyld))
            return f

        if fn is None and dyld is not None:
            return attach(None)
        if fn is None:
            return attach  # decorator form
        return attach(fn)

    def _wrap_cpu_body(self, f: Callable) -> Callable:
        g_ns = self._ptg._g_ns

        def hook(es: Any, task: Any) -> int:
            rc = f(es, task, g_ns(), _ns(task.locals))
            return HOOK_RETURN_DONE if rc is None else rc

        return hook

    # -- helpers ------------------------------------------------------------
    def _mk_dep(self, ref: tuple | None, data: tuple | None,
                guard: Callable | None, dtt: Any,
                new: bool = False, null: bool = False,
                ranged: bool = False, wire: Any = None) -> Dep:
        # every wrapper takes a locals dict or the namespace a caller has
        # already built of it (``_ns`` passes that through); the globals
        # view is one live object for the builder's life
        g = self._ptg._g_view
        gfn = None
        if guard is not None:
            gfn = lambda locals_: guard(g, _ns(locals_))
        wfn = wire
        if callable(wire):
            wfn = lambda locals_: wire(g, _ns(locals_))
        if new or null:
            # NEW: all targets None — resolve_data_inputs leaves the slot
            # empty and prepare_input allocates scratch of the flow type;
            # NULL: the flow explicitly carries no data for these locals
            return Dep(guard=gfn, dtt=dtt, null=null)
        if ref is not None:
            cls_name, flow_name, params_fn = ref
            tparams = lambda locals_: params_fn(g, _ns(locals_))
            if callable(flow_name):
                # a flow of a family (one a tile row), named per instance:
                # ``fn(g, l)`` of the producer's locals on an output, of the
                # consumer's on an input (``Dep.flow_name``)
                name_fn = flow_name
                flow_name = lambda locals_: name_fn(g, _ns(locals_))
            return Dep(guard=gfn, target_class=cls_name,
                       target_flow=flow_name, target_params=tparams, dtt=dtt,
                       ranged=ranged, wire=wfn)
        if data is not None:
            collection, key_fn = data
            dc_get = self._ptg._dc_getter(collection)

            def data_ref(locals_: dict) -> tuple:
                key = key_fn(g, _ns(locals_))
                if not isinstance(key, tuple):
                    key = (key,)
                return dc_get(), key

            return Dep(guard=gfn, data_ref=data_ref, dtt=dtt, wire=wfn)
        # pure CTL arrow with neither: invalid
        raise ValueError("dep needs a task ref or a data ref")

    def _enumerate_space(self) -> Iterable[dict]:
        """Yield every locals assignment in the execution space."""
        g = self._ptg._g_ns()
        names = list(self.param_ranges)

        def rec(i: int, partial: dict):
            if i == len(names):
                yield dict(partial)
                return
            name = names[i]
            for v in self.param_ranges[name](g, _ns(partial)):
                partial[name] = v
                yield from rec(i + 1, partial)
            partial.pop(name, None)

        yield from rec(0, {})

    def _build(self) -> TaskClass:
        tc = TaskClass(
            self.name,
            params=list(self.param_ranges),
            flows=[fb._build() for fb in self._flows],
            chores=list(self._chores),
            affinity=self._affinity,
            priority=self._priority,
            time_estimate=self._time_estimate,
            make_key_fn=self._make_key,
            find_deps_fn=self._find_deps,
            hash_struct=self._hash_struct,
            startup_fn=self._startup,
            simcost=self._simcost,
        )
        # device-task transfer overrides ride as plain attributes (the
        # device module reads them per dispatch; absent = defaults)
        if self._stage_in_hook is not None:
            tc.stage_in_hook = self._stage_in_hook
        if self._stage_out_hook is not None:
            tc.stage_out_hook = self._stage_out_hook
        tc.pad_rows = self._pad_rows
        tc.batch_max = self._batch_max

        # execution-space membership (the generated bounds-check role):
        # parameters validate in declaration order against their ranges.
        # This sits on the release hot path (one call per successor edge),
        # so locals-INDEPENDENT ranges — the overwhelmingly common case —
        # are captured once at first use (range membership is O(1));
        # dependent ranges re-evaluate in order.  Mutating the pool's
        # globals after execution starts is outside the contract anyway.
        g_ns = self._ptg._g_ns
        g_view = self._ptg._g_view
        ranges = self.param_ranges
        cache: dict = {"tests": None}

        class _Poison:
            def __getattr__(self, k):
                raise LookupError(k)

            def __getitem__(self, k):
                raise LookupError(k)

        # static box extents for the index-array dep-storage variant
        # (parsec_default_find_deps / `-M index-array`): captured lazily
        # at first use — like in_space's static capture below, so globals
        # bound between build() and execution start are honored
        def extents_fn() -> tuple | None:
            try:
                g = g_ns()
                st = tuple(rngfn(g, _Poison())
                           for rngfn in ranges.values())
                if all(isinstance(r, range) and r.step == 1 for r in st):
                    return tuple((r.start, r.stop) for r in st)
            except Exception:
                pass
            return None

        tc.space_extents_fn = extents_fn

        def in_space(locals_: Any) -> bool:
            # a dict, or the namespace the release path built of it once
            if locals_.__class__ is _NS:
                ns, d = locals_, locals_.__dict__
            else:
                ns, d = None, locals_
            tests = cache["tests"]
            if tests is None:
                # per parameter: the range itself where it reads no local
                # (captured once), else the function to ask again
                poison = _Poison()
                tests = []
                for pname, rngfn in ranges.items():
                    try:
                        tests.append((pname, rngfn(g_view, poison), None))
                    except Exception:
                        tests.append((pname, None, rngfn))
                tests = cache["tests"] = tuple(tests)
            for pname, r, rngfn in tests:
                v = d.get(pname)
                if v is None:
                    return False
                if rngfn is not None:
                    # a dependent range (a triangular space) reads the
                    # parameters declared before its own, all of which
                    # the one namespace of the locals holds
                    if ns is None:
                        ns = _NS(**d)
                    r = rngfn(g_view, ns)
                if v not in r:
                    return False
            return True

        tc.in_space = in_space
        tc.locals_view = _ns
        return tc


class PTGTaskpool(Taskpool):
    """A taskpool generated from a PTG description."""

    def __init__(self, name: str, builder: "PTGBuilder") -> None:
        super().__init__(name=name)
        self._builder = builder
        self._tc_builders: dict[str, TaskClassBuilder] = {}

    @property
    def globals(self) -> Any:
        """The bound JDF/builder globals as a namespace — what generated
        code reaches through ``__parsec_tp->super._g_<name>``; UD override
        functions receive the pool and read problem sizes through this."""
        return self._builder._g_ns()

    def validate(self, nb_ranks: int | None = None,
                 raise_on_error: bool = True) -> Any:
        """Statically verify this pool's dataflow (analysis.graphcheck):
        edge symmetry, access consistency, cycles, tile/rank bounds — the
        ``parsec_ptgpp`` compile-time contract, without executing a kernel.
        Returns the :class:`~parsec_tpu.analysis.GraphReport`; raises
        :class:`~parsec_tpu.analysis.GraphCheckError` in gate mode."""
        from ..analysis import check_ptg
        report = check_ptg(self, nb_ranks=nb_ranks)
        if raise_on_error:
            report.raise_if_failed()
        return report

    def nb_local_tasks(self) -> int:
        """Count tasks whose affinity lands on this rank (generated
        ``nb_local_tasks_fn`` analog); a pool-level UD override replaces
        the scan entirely."""
        if self._builder._nb_local_tasks_fn is not None:
            return int(self._builder._nb_local_tasks_fn(self))
        my_rank = self.context.my_rank if self.context else 0
        multi = (self.context is not None and self.context.nb_ranks > 1
                 and not self.local_only)
        n = 0
        for tc in self.task_classes:
            tcb = self._tc_builders[tc.name]
            for locals_ in tcb._enumerate_space():
                if multi and tc.affinity is not None:
                    dc, key = tc.affinity(locals_)
                    if not isinstance(key, tuple):
                        key = (key,)
                    if dc.rank_of(*key) != my_rank:
                        continue
                n += 1
        return n

    def startup(self, context: Any) -> list:
        """Enumerate initially-ready local tasks (empty IN-dep mask)."""
        from ..runtime.scheduling import resolve_data_inputs
        from ..runtime.task import Task
        multi = context.nb_ranks > 1 and not self.local_only
        out = []
        for tc in self.task_classes:
            tcb = self._tc_builders[tc.name]
            if tc.startup_fn is not None:
                # UD startup (JDF_PROP_UD_STARTUP_TASKS_FN_NAME): the user
                # enumerates the initially-ready instances themselves
                space = tc.startup_fn(self, context, tcb._ptg._g_ns())
            else:
                space = (l for l in tcb._enumerate_space()
                         if tc.input_dep_mask(l) == 0)
            for locals_ in space:
                if multi and tc.affinity is not None:
                    dc, key = tc.affinity(locals_)
                    if not isinstance(key, tuple):
                        key = (key,)
                    if dc.rank_of(*key) != my_rank_of(context):
                        continue
                prio = tc.priority(locals_) if tc.priority else 0
                t = Task(self, tc, dict(locals_), priority=prio)
                t.status = "ready"
                resolve_data_inputs(t)  # snapshot collection reads now
                out.append(t)
        return out


def my_rank_of(context: Any) -> int:
    return context.my_rank


class PTGBuilder:
    """Top-level builder: globals + task classes → :class:`PTGTaskpool`.

    Globals mirror JDF globals (problem sizes, collections); they are late
    bound so a built taskpool template can be re-parameterized.
    """

    def __init__(self, name: str, **globals_) -> None:
        self.name = name
        self.globals = dict(globals_)
        self._classes: list[TaskClassBuilder] = []
        self._g_view = _DictNS(self.globals)
        self._nb_local_tasks_fn: Callable | None = None
        self._termdet: str | None = None

    def global_(self, **kw) -> "PTGBuilder":
        self.globals.update(kw)
        return self

    def option(self, nb_local_tasks_fn: Callable | None = None,
               termdet: str | None = None) -> "PTGBuilder":
        """Pool-level UD options (JDF ``%option`` analog):
        ``nb_local_tasks_fn(taskpool) -> int`` replaces the execution-space
        scan (``JDF_PROP_UD_NB_LOCAL_TASKS_FN_NAME``); ``termdet`` selects
        this pool's termination detector (``JDF_PROP_TERMDET_NAME``)."""
        if nb_local_tasks_fn is not None:
            self._nb_local_tasks_fn = nb_local_tasks_fn
        if termdet is not None:
            self._termdet = termdet
        return self

    def _g_ns(self) -> _DictNS:
        return self._g_view   # live view: global updates stay visible

    def _dc_getter(self, collection: Any) -> Callable[[], Any]:
        if isinstance(collection, str):
            return lambda: self.globals[collection]
        return lambda: collection

    def task(self, name: str, **params: Callable) -> TaskClassBuilder:
        tcb = TaskClassBuilder(self, name, params)
        self._classes.append(tcb)
        return tcb

    def build(self) -> PTGTaskpool:
        tp = PTGTaskpool(self.name, self)
        tp.termdet_name = self._termdet
        for tcb in self._classes:
            tc = tp.add_task_class(tcb._build())
            tp._tc_builders[tc.name] = tcb
        return tp


# convenience range constructors mirroring JDF "low .. high" syntax
def span(low: Callable | int, high: Callable | int, step: int = 1) -> Callable:
    """Inclusive range ``low .. high`` like JDF execution-space ranges."""

    def rng(g: _NS, l: _NS) -> range:
        lo = low(g, l) if callable(low) else low
        hi = high(g, l) if callable(high) else high
        return range(lo, hi + 1, step)

    return rng
