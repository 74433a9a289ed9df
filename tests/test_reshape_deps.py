"""Reshape/datatype-on-deps (VERDICT r2 item 6).

A dep may declare a TileType (``dtt=`` in the DSL, ``[type=NAME]`` in JDF);
the consumer of that edge observes the datum converted — lazily, shared per
(copy, type), on the read side — while the producer's copy stays untouched.
Covers: local task edges, collection reads, writebacks, the remote receive
path on 2 ranks (the reference's remote_read_reshape shape), and the
compiled-path opt-outs.
"""

import numpy as np
import pytest

from parsec_tpu import ptg
from parsec_tpu.comm import run_multirank
from parsec_tpu.data.data import TileType
from parsec_tpu.data.datatype import register_layout
from parsec_tpu.data.reshape import needs_reshape, reshaped_future
from parsec_tpu.data_dist.collection import DictCollection
from parsec_tpu.data_dist.matrix import TwoDimBlockCyclic
from parsec_tpu.runtime import Context

F32 = np.float32

# a transposed layout: canonical <-> transposed via .T (involution)
register_layout("transposed", lambda x: x.T, lambda x: x.T)

VEC8 = TileType((8,), F32)
MAT24 = TileType((2, 4), F32)
MAT42 = TileType((4, 2), F32)
F64_8 = TileType((8,), np.float64)
TRANS = TileType((4, 2), F32, layout="transposed")


def run_pool(tp):
    ctx = Context(nb_cores=0)
    ctx.add_taskpool(tp)
    ctx.wait(timeout=60)
    ctx.fini()


def coll(name, value):
    v = np.asarray(value, F32)
    return DictCollection(name, dtt=TileType(v.shape, v.dtype),
                          init_fn=lambda *k: v.copy())


class TestLocalEdges:
    def build(self, A, in_dtt=None, out_dtt=None, seen=None):
        """P -> C over one tile; the P->C edge may be typed on either end."""
        p = ptg.PTGBuilder("ty", A=A)
        t = p.task("P", i=ptg.span(0, 0))
        f = t.flow("V", ptg.RW)
        f.input(data=("A", lambda g, l: (0,)))
        f.output(succ=("C", "V", lambda g, l: {"i": 0}), dtt=out_dtt)
        t.body(lambda es, task, g, l: None)
        c = p.task("C", i=ptg.span(0, 0))
        fc = c.flow("V", ptg.READ)
        fc.input(pred=("P", "V", lambda g, l: {"i": 0}), dtt=in_dtt)

        def cbody(es, task, g, l):
            seen.append(np.asarray(task.flow_data("V").value))

        c.body(cbody)
        return p.build()

    def test_out_dep_type_reshapes(self):
        seen = []
        A = coll("A", np.arange(8))
        run_pool(self.build(A, out_dtt=MAT24, seen=seen))
        np.testing.assert_array_equal(seen[0],
                                      np.arange(8, dtype=F32).reshape(2, 4))

    def test_in_dep_type_wins_over_out(self):
        seen = []
        A = coll("A", np.arange(8))
        run_pool(self.build(A, out_dtt=MAT24, in_dtt=MAT42, seen=seen))
        assert seen[0].shape == (4, 2)

    def test_dtype_conversion(self):
        seen = []
        A = coll("A", np.arange(8))
        run_pool(self.build(A, in_dtt=F64_8, seen=seen))
        assert seen[0].dtype == np.float64

    def test_layout_conversion(self):
        seen = []
        A = coll("A", np.arange(8))
        run_pool(self.build(A, in_dtt=TRANS, seen=seen))
        # from_canonical of "transposed" transposes the (4,2) reshape
        np.testing.assert_array_equal(
            seen[0], np.arange(8, dtype=F32).reshape(4, 2).T)

    def test_producer_copy_untouched(self):
        seen = []
        A = coll("A", np.arange(8))
        run_pool(self.build(A, out_dtt=MAT24, seen=seen))
        home = np.asarray(A.data_of(0).newest_copy().value)
        assert home.shape == (8,)   # read-side reshape: source unchanged

    def test_conversion_shared_across_consumers(self):
        """Two typed consumers of one copy share a single conversion."""
        A = coll("A", np.arange(8))
        calls = []
        register_layout("counted",
                        lambda x: x,
                        lambda x: (calls.append(1), x)[1])
        CT = TileType((8,), F32, layout="counted")
        p = ptg.PTGBuilder("sh", A=A)
        t = p.task("P", i=ptg.span(0, 0))
        f = t.flow("V", ptg.RW)
        f.input(data=("A", lambda g, l: (0,)))
        f.output(succ=("C", "V", lambda g, l: {"i": 0}), dtt=CT)
        f.output(succ=("D", "V", lambda g, l: {"i": 0}), dtt=CT)
        t.body(lambda es, task, g, l: None)
        for name in ("C", "D"):
            c = p.task(name, i=ptg.span(0, 0))
            c.flow("V", ptg.READ).input(
                pred=("P", "V", lambda g, l: {"i": 0}))
            c.body(lambda es, task, g, l: None)
        run_pool(p.build())
        assert len(calls) == 1

    def test_collection_read_with_type(self):
        seen = []
        A = coll("A", np.arange(8))
        p = ptg.PTGBuilder("cr", A=A)
        t = p.task("T", i=ptg.span(0, 0))
        t.flow("V", ptg.READ).input(data=("A", lambda g, l: (0,)),
                                    dtt=MAT24)
        t.body(lambda es, task, g, l:
               seen.append(np.asarray(task.flow_data("V").value)))
        run_pool(p.build())
        assert seen[0].shape == (2, 4)

    def test_writeback_with_type(self):
        A = coll("A", np.arange(8))
        B = coll("B", np.zeros((2, 4)))
        p = ptg.PTGBuilder("wb", A=A, B=B)
        t = p.task("T", i=ptg.span(0, 0))
        f = t.flow("V", ptg.RW)
        f.input(data=("A", lambda g, l: (0,)))
        f.output(data=("B", lambda g, l: (0,)), dtt=MAT24)
        t.body(lambda es, task, g, l: None)
        run_pool(p.build())
        got = np.asarray(B.data_of(0).newest_copy().value)
        np.testing.assert_array_equal(got,
                                      np.arange(8, dtype=F32).reshape(2, 4))


class TestRemote:
    def test_remote_read_reshape_on_2_ranks(self):
        """The reference's remote_read_reshape shape: rank 0 produces a
        vector tile; rank 1's consumer declares [type=(2,4)] on its input
        dep and must observe the converted matrix."""

        def body(ctx, rank, nranks):
            A = TwoDimBlockCyclic("A8", lm=2 * 8, ln=1, mb=8, nb=1,
                                  P=2, Q=1, myrank=rank,
                                  init_fn=lambda m, n, sh:
                                  np.arange(8, dtype=F32).reshape(sh)
                                  if sh == (8, 1) else np.zeros(sh, F32))
            seen = []
            p = ptg.PTGBuilder("rr", A=A)
            t = p.task("P", i=ptg.span(0, 0))
            t.affinity("A", lambda g, l: (0, 0))
            f = t.flow("V", ptg.RW)
            f.input(data=("A", lambda g, l: (0, 0)))
            f.output(succ=("C", "V", lambda g, l: {"i": 0}))
            t.body(lambda es, task, g, l: None)
            c = p.task("C", i=ptg.span(0, 0))
            c.affinity("A", lambda g, l: (1, 0))   # lives on rank 1
            c.flow("V", ptg.READ).input(
                pred=("P", "V", lambda g, l: {"i": 0}),
                dtt=TileType((2, 4), F32))
            c.body(lambda es, task, g, l:
                   seen.append(np.asarray(task.flow_data("V").value)))
            ctx.add_taskpool(p.build())
            ctx.wait(timeout=60)
            ctx.comm_barrier()
            return seen[0] if seen else None

        res = run_multirank(2, body)
        assert res[0] is None          # consumer ran on rank 1 only
        assert res[1].shape == (2, 4)
        np.testing.assert_array_equal(
            res[1], np.arange(8, dtype=F32).reshape(2, 4))


class TestJDF:
    def test_jdf_type_property(self):
        from parsec_tpu.ptg.jdf import parse_jdf
        src = """
        A   [type = data]
        B   [type = data]
        M24 [type = int]

        T(i)
          i = 0 .. 0
          : A(0)
          RW V <- A(0)
               -> B(0) [type = M24]
        BODY
          pass
        END
        """
        A = coll("A", np.arange(8))
        B = coll("B", np.zeros((2, 4)))
        tp = parse_jdf(src, "ty").build(A=A, B=B, M24=MAT24)
        run_pool(tp)
        got = np.asarray(B.data_of(0).newest_copy().value)
        np.testing.assert_array_equal(got,
                                      np.arange(8, dtype=F32).reshape(2, 4))

    def test_jdf_type_must_be_tiletype(self):
        from parsec_tpu.ptg.jdf import JDFError, parse_jdf
        src = """
        A  [type = data]
        X  [type = int]

        T(i)
          i = 0 .. 0
          : A(0)
          RW V <- A(0)
               -> A(0) [type = X]
        BODY
          pass
        END
        """
        with pytest.raises(JDFError):
            parse_jdf(src, "bad").build(A=coll("A", np.arange(8)), X=7)


class TestOptOuts:
    def mk(self):
        A = coll("A", np.arange(8))
        p = ptg.PTGBuilder("oo", A=A)
        t = p.task("P", i=ptg.span(0, 0))
        f = t.flow("V", ptg.RW)
        f.input(data=("A", lambda g, l: (0,)))
        f.output(succ=("C", "V", lambda g, l: {"i": 0}), dtt=MAT24)
        t.body(lambda es, task, g, l: None)
        c = p.task("C", i=ptg.span(0, 0))
        c.flow("V", ptg.READ).input(pred=("P", "V", lambda g, l: {"i": 0}))
        c.body(lambda es, task, g, l: None)
        return p.build()

    def test_lowering_refuses_typed_edges(self):
        from parsec_tpu.ptg.lowering import LoweringError, lower_taskpool
        with pytest.raises(LoweringError):
            lower_taskpool(self.mk())

    def test_cache_invalidated_on_version_bump(self):
        """A writeback mutates the home copy in place; a later typed read
        must convert the NEW value, not serve the stale cached repack."""
        A = coll("A", np.arange(8))
        copy = A.data_of(0).newest_copy()
        first = reshaped_future(copy, MAT24).get()
        np.testing.assert_array_equal(np.asarray(first.value).ravel(),
                                      np.arange(8, dtype=F32))
        copy.value = np.arange(100, 108, dtype=F32)
        copy.version += 1
        second = reshaped_future(copy, MAT24).get()
        np.testing.assert_array_equal(np.asarray(second.value).ravel(),
                                      np.arange(100, 108, dtype=F32))

    def test_untyped_writeback_restores_home_type(self):
        """A flow whose INPUT was reshaped must not write the converted
        shape back through an untyped output arrow."""
        A = coll("A", np.arange(8))
        p = ptg.PTGBuilder("uwb", A=A)
        t = p.task("T", i=ptg.span(0, 0))
        f = t.flow("V", ptg.RW)
        f.input(data=("A", lambda g, l: (0,)), dtt=MAT24)
        f.output(data=("A", lambda g, l: (0,)))   # untyped writeback

        def body(es, task, g, l):
            v = task.flow_data("V")
            assert np.asarray(v.value).shape == (2, 4)
            v.value = np.asarray(v.value) + 100
            v.version += 1

        t.body(body)
        run_pool(p.build())
        home = np.asarray(A.data_of(0).newest_copy().value)
        assert home.shape == (8,)   # home type restored
        np.testing.assert_array_equal(home,
                                      np.arange(8, dtype=F32) + 100)

    def test_helpers(self):
        A = coll("A", np.arange(8))
        copy = A.data_of(0).newest_copy()
        assert not needs_reshape(copy, None)
        assert not needs_reshape(copy, VEC8)
        assert needs_reshape(copy, MAT24)
        f1 = reshaped_future(copy, MAT24)
        f2 = reshaped_future(copy, MAT24)
        assert f1 is f2                      # shared per (copy, type)
        out = f1.get()
        assert np.asarray(out.value).shape == (2, 4)
