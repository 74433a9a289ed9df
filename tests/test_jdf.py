"""JDF textual front-end tests.

Mirrors the reference's DSL tier (SURVEY §4): working JDFs (chain with
guarded ternary arrows, CTL-only EP, GEMM equivalence against the builder
API) plus the must-fail compilations of the ``ptgpp`` error-case suite.
"""

import numpy as np
import pytest

from parsec_tpu.comm import run_multirank
from parsec_tpu.data_dist.matrix import TiledMatrix, VectorTwoDimCyclic
from parsec_tpu.ptg import JDFError, parse_jdf
from parsec_tpu.runtime import Context


CHAIN_JDF = """
/* Ex04_ChainData analog: a value threads tile V(0) through NT tasks */
NT   [type = int]
V    [type = data]

T(i)
  i = 0 .. NT-1
  : V(i)
  RW A <- (i == 0) ? V(0) : A T(i-1)
       -> (i < NT-1) ? A T(i+1) : V(0)
BODY
  A += 1
END
"""


def test_chain_jdf_single_rank():
    V = VectorTwoDimCyclic("V", lm=8, mb=2, P=1,
                           init_fn=lambda m, size: np.zeros(size))
    tp = parse_jdf(CHAIN_JDF, name="chain").build(NT=4, V=V)
    with Context(nb_cores=0) as ctx:
        ctx.add_taskpool(tp)
        ctx.wait(timeout=30)
    np.testing.assert_allclose(V.data_of(0).newest_copy().value,
                               np.full(2, 4.0))


def _chain_jdf_body(ctx, rank, nranks):
    V = VectorTwoDimCyclic("V", lm=12, mb=2, P=nranks, myrank=rank,
                           init_fn=lambda m, size: np.zeros(size))
    tp = parse_jdf(CHAIN_JDF, name="chain").build(NT=6, V=V)
    ctx.add_taskpool(tp)
    ctx.wait(timeout=60)
    ctx.comm_barrier()
    if rank == 0:
        return np.asarray(V.data_of(0).newest_copy().value).copy()
    return None


def test_chain_jdf_multirank():
    res = run_multirank(3, _chain_jdf_body)
    np.testing.assert_allclose(res[0], np.full(2, 6.0))


EP_JDF = """
NT     [type = int]
DEPTH  [type = int]
V      [type = data]

EP(d, n)
  d = 0 .. DEPTH-1
  n = 0 .. NT-1
  : V(n)
  CTL X <- (d > 0) ? X EP(d-1, n)
        -> (d < DEPTH-1) ? X EP(d+1, n)
BODY
  task.taskpool.counter += 1
END
"""


def test_ep_jdf_ctl_only():
    """The scheduler micro-benchmark shape (tests/runtime/scheduling/ep.jdf):
    CTL-only DAG, NT independent depth-DEPTH chains."""
    V = VectorTwoDimCyclic("V", lm=4, mb=1, P=1,
                           init_fn=lambda m, size: np.zeros(size))
    tp = parse_jdf(EP_JDF, name="ep").build(NT=4, DEPTH=5, V=V)
    tp.counter = 0
    with Context(nb_cores=0) as ctx:
        ctx.add_taskpool(tp)
        ctx.wait(timeout=30)
    assert tp.counter == 4 * 5


GEMM_JDF = """
%{
import numpy as np
%}
A [type = data]
B [type = data]
C [type = data]
MT [type = int]
NT [type = int]
KT [type = int]

GEMM(m, n, k)
  m = 0 .. MT-1
  n = 0 .. NT-1
  k = 0 .. KT-1
  : C(m, n)
  READ X <- A(m, k)
  READ Y <- B(k, n)
  RW   Z <- (k == 0) ? C(m, n) : Z GEMM(m, n, k-1)
        -> (k < KT-1) ? Z GEMM(m, n, k+1) : C(m, n)
  ; KT - k
BODY
  Z += X @ Y
END
"""


def test_gemm_jdf_matches_numpy():
    rng = np.random.default_rng(1)
    n, nb = 48, 16
    A = rng.standard_normal((n, n)).astype(np.float32)
    B = rng.standard_normal((n, n)).astype(np.float32)
    dA = TiledMatrix.from_dense("A", A, nb, nb)
    dB = TiledMatrix.from_dense("B", B, nb, nb)
    dC = TiledMatrix.from_dense("C", np.zeros((n, n), np.float32), nb, nb)
    tp = parse_jdf(GEMM_JDF, name="gemm").build(
        A=dA, B=dB, C=dC, MT=dC.mt, NT=dC.nt, KT=dA.nt)
    with Context(nb_cores=2) as ctx:
        ctx.add_taskpool(tp)
        ctx.wait(timeout=60)
    np.testing.assert_allclose(dC.to_dense(), A @ B, rtol=1e-4, atol=1e-4)


def test_prologue_and_defaults():
    src = """
%{
def double(x):
    return 2 * x
%}
N = double(3) [type = int]
V [type = data]

T(i)
  i = 0 .. N-1
  : V(0)
  RW A <- (i == 0) ? V(0) : A T(i-1)
       -> (i < N-1) ? A T(i+1) : V(0)
BODY
  A += double(1)
END
"""
    V = VectorTwoDimCyclic("V", lm=1, mb=1, P=1,
                           init_fn=lambda m, size: np.zeros(size))
    tp = parse_jdf(src).build(V=V)   # N defaults to double(3) == 6
    with Context(nb_cores=0) as ctx:
        ctx.add_taskpool(tp)
        ctx.wait(timeout=30)
    np.testing.assert_allclose(V.data_of(0).newest_copy().value, [12.0])


def test_functional_rebind_body():
    """A body that rebinds a flow name gets the new array written back."""
    src = """
V [type = data]

T(i)
  i = 0 .. 0
  : V(0)
  RW A <- V(0)
       -> V(0)
BODY
  A = A + 41.0
END
"""
    V = VectorTwoDimCyclic("V", lm=1, mb=1, P=1,
                           init_fn=lambda m, size: np.ones(size))
    tp = parse_jdf(src).build(V=V)
    with Context(nb_cores=0) as ctx:
        ctx.add_taskpool(tp)
        ctx.wait(timeout=30)
    np.testing.assert_allclose(V.data_of(0).newest_copy().value, [42.0])


def test_floor_division_survives_everywhere():
    """'//' is Python floor division in expressions/bodies, never a trailing
    comment; only full-line '//' and '/* */' are comments."""
    src = """
// a full-line comment
/* a block
   comment */
N [type = int]
V [type = data]

T(i)
  i = 0 .. N // 2
  : V(0)
  RW A <- (i == 0) ? V(0) : A T(i-1)
       -> (i < N // 2) ? A T(i+1) : V(0)
BODY
  A += i // 2    # floor division inside a python body
END
"""
    V = VectorTwoDimCyclic("V", lm=1, mb=1, P=1,
                           init_fn=lambda m, size: np.zeros(size))
    tp = parse_jdf(src).build(N=8, V=V)   # i = 0..4
    with Context(nb_cores=0) as ctx:
        ctx.add_taskpool(tp)
        ctx.wait(timeout=30)
    expect = sum(i // 2 for i in range(5))
    np.testing.assert_allclose(V.data_of(0).newest_copy().value, [expect])


def test_descending_range_and_comprehension_expr():
    """Negative-step ranges include the low endpoint; comprehensions inside
    expressions can see JDF parameters/globals."""
    src = """
N [type = data]
V [type = data]

T(i)
  i = 3 .. 0 .. -1
  : V(0)
  RW A <- (i == 3) ? V(0) : A T(i+1)
       -> (i > 0) ? A T(i-1) : V(0)
  ; sum(j for j in range(i))
BODY
  A[0] = A[0] * 10 + i
END
"""
    V = VectorTwoDimCyclic("V", lm=1, mb=1, P=1,
                           init_fn=lambda m, size: np.zeros(size))
    jdf = parse_jdf(src)
    tp = jdf.build(N=V, V=V)
    with Context(nb_cores=0) as ctx:
        ctx.add_taskpool(tp)
        ctx.wait(timeout=30)
    # chain runs i = 3, 2, 1, 0 -> digits appended in that order
    np.testing.assert_allclose(V.data_of(0).newest_copy().value, [3210.0])


def test_global_named_like_body():
    """Identifiers beginning with BODY are not the BODY keyword."""
    src = """
BODY_SIZE [type = int]
V [type = data]

T(i)
  i = 0 .. BODY_SIZE - 1
  : V(0)
  RW A <- V(0)
       -> V(0)
BODY
  A += 1
END
"""
    V = VectorTwoDimCyclic("V", lm=1, mb=1, P=1,
                           init_fn=lambda m, size: np.zeros(size))
    tp = parse_jdf(src).build(BODY_SIZE=1, V=V)
    assert tp.task_class("T") is not None


def test_fail_write_flow_task_input_in_else_branch():
    _must_fail("""
V [type = data]
T(i)
  i = 0 .. 3
  : V(0)
  WRITE A <- (i == 0) ? V(0) : A T(i-1)
        -> V(0)
BODY
END
""", "WRITE flow", V=object())


# ---------------------------------------------------------------------------
# must-fail suite (the ptgpp NODEFAULTBUILD error cases, SURVEY §4)
# ---------------------------------------------------------------------------

def _must_fail(src, match, **bindings):
    with pytest.raises(JDFError, match=match):
        parse_jdf(src).build(**bindings)


def test_fail_unknown_target_class():
    _must_fail("""
V [type = data]
T(i)
  i = 0 .. 3
  : V(0)
  RW A <- V(0) -> A NOPE(i+1)
BODY
END
""", "unknown task class", V=object())


def test_fail_unknown_flow_on_target():
    _must_fail("""
V [type = data]
T(i)
  i = 0 .. 3
  : V(0)
  RW A <- V(0) -> (i < 3) ? B T(i+1) : V(0)
BODY
END
""", "has no flow", V=object())


def test_fail_missing_range():
    _must_fail("""
V [type = data]
T(i, j)
  i = 0 .. 3
  : V(0)
  RW A <- V(0) -> V(0)
BODY
END
""", "has no range", V=object())


def test_fail_ctl_with_data():
    _must_fail("""
V [type = data]
T(i)
  i = 0 .. 3
  : V(0)
  CTL X <- V(0)
BODY
END
""", "CTL flow", V=object())


def test_fail_missing_body():
    _must_fail("""
V [type = data]
T(i)
  i = 0 .. 3
  : V(0)
  RW A <- V(0) -> V(0)
""", "no BODY", V=object())


def test_fail_unbound_global():
    _must_fail("""
N [type = int]
V [type = data]
T(i)
  i = 0 .. N-1
  : V(0)
  RW A <- V(0) -> V(0)
BODY
END
""", "needs a value", V=object())


def test_fail_body_without_end():
    with pytest.raises(JDFError, match="without END"):
        parse_jdf("""
V [type = data]
T(i)
  i = 0 .. 3
  : V(0)
  RW A <- V(0) -> V(0)
BODY
  pass
""")


def test_fail_affinity_not_data():
    _must_fail("""
N [type = int]
T(i)
  i = 0 .. 3
  : N(0)
  RW A <- N(0) -> N(0)
BODY
END
""", "not a .type = data. global", N=4)


def test_fail_write_flow_task_input():
    _must_fail("""
V [type = data]
T(i)
  i = 0 .. 3
  : V(0)
  WRITE A <- A T(i-1)
        -> V(0)
BODY
END
""", "WRITE flow", V=object())


class TestNewNullTargets:
    """JDF NEW/NULL endpoints (reference jdf.h special targets; Ex03's
    `<- NEW` first-link form is the SURVEY §7 step-3 exit shape)."""

    def test_ex03_shape_with_new(self):
        """The reference Ex03_ChainMPI.jdf chain: the first task allocates
        its datum with NEW, every later task receives it from its
        predecessor, incrementing as it goes."""
        import numpy as np

        from parsec_tpu.data.data import TileType
        from parsec_tpu.runtime import Context

        src = """
        NB    [type = int]
        T1    [type = int]
        SINK  [type = int]

        Task(k)
          k = 0 .. NB
          RW A <- (k == 0) ? NEW : A Task(k - 1)  [type = T1]
               -> (k < NB) ? A Task(k + 1)
        BODY
          if k == 0:
              A[...] = 0
          else:
              A[...] = A + 1
          if k == NB:
              SINK.append(float(A[0]))
        END
        """
        sink = []
        tp = parse_jdf(src, "ex03new").build(
            NB=7, T1=TileType((1,), np.float32), SINK=sink)
        ctx = Context(nb_cores=0)
        ctx.add_taskpool(tp)
        ctx.wait(timeout=30)
        ctx.fini()
        assert sink == [7.0]

    def test_null_input_and_output(self):
        import numpy as np

        from parsec_tpu.data.data import TileType
        from parsec_tpu.data_dist.collection import DictCollection
        from parsec_tpu.runtime import Context

        src = """
        A     [type = data]
        SINK  [type = int]

        T(i)
          i = 0 .. 1
          : A(0)
          RW V <- (i == 0) ? A(0) : NULL
               -> NULL
        BODY
          SINK.append(V is None)
        END
        """
        coll = DictCollection("A", dtt=TileType((1,), np.float32),
                              init_fn=lambda *k: np.zeros(1, np.float32))
        sink = []
        tp = parse_jdf(src, "nulls").build(A=coll, SINK=sink)
        ctx = Context(nb_cores=0)
        ctx.add_taskpool(tp)
        ctx.wait(timeout=30)
        ctx.fini()
        assert sorted(sink) == [False, True]   # i=0 got data, i=1 NULL

    def test_new_without_type_rejected(self):
        src = """
        NB [type = int]

        T(i)
          i = 0 .. 0
          RW V <- NEW
        BODY
          pass
        END
        """
        with pytest.raises(JDFError, match="NEW needs"):
            parse_jdf(src, "badnew").build(NB=1)

    def test_new_on_ctl_flow_rejected_with_line(self):
        src = """
        NB [type = int]

        T(i)
          i = 0 .. 0
          CTL X <- NEW
        BODY
          pass
        END
        """
        with pytest.raises(JDFError, match=r"line \d+: CTL flow X"):
            parse_jdf(src, "badctlnew").build(NB=1)

    def test_new_as_output_rejected(self):
        src = """
        NB [type = int]

        T(i)
          i = 0 .. 0
          RW V -> NEW
        BODY
          pass
        END
        """
        with pytest.raises(JDFError, match="input-only"):
            parse_jdf(src, "badout").build(NB=1)

    def test_lowering_refuses_new_null_gracefully(self):
        import numpy as np

        from parsec_tpu import ptg
        from parsec_tpu.data.data import TileType
        from parsec_tpu.ptg.lowering import LoweringError, lower_taskpool

        p = ptg.PTGBuilder("nn", N=2)
        t = p.task("T", i=ptg.span(0, lambda g, l: g.N - 1))
        f = t.flow("V", ptg.RW)
        f.input(new=True, guard=lambda g, l: l.i == 0,
                dtt=TileType((1,), np.float32))
        f.input(null=True, guard=lambda g, l: l.i > 0)
        t.body(lambda es, task, g, l: None, dyld="gemm")
        with pytest.raises(LoweringError):
            lower_taskpool(p.build())

    def test_dsl_new_without_type_rejected(self):
        from parsec_tpu import ptg

        p = ptg.PTGBuilder("nt", N=1)
        t = p.task("T", i=ptg.span(0, 0))
        f = t.flow("V", ptg.RW)
        with pytest.raises(ValueError, match="NEW needs"):
            f.input(new=True)
