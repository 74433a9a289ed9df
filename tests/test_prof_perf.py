"""Perf-observability polish: print_steals + alperf PINS modules, the
CPU cache-topology feed (hwloc distance role), and the JDF unparser
round-trip (jdf_unparse role).
"""

import time

import numpy as np

from parsec_tpu import ptg
from parsec_tpu.core.mca import repository
from parsec_tpu.core.topology import (core_of_stream, distance, llc_group_of,
                                      llc_groups)
from parsec_tpu.prof.counters import sde
from parsec_tpu.runtime import Context


# ---------------------------------------------------------------------------
# topology
# ---------------------------------------------------------------------------

def test_llc_groups_cover_and_agree():
    groups = llc_groups()
    assert groups, "no topology groups at all"
    seen = set()
    for g in groups:
        assert not (seen & g), "a cpu in two LLC groups"
        seen |= g
    for cpu in list(seen)[:8]:
        assert cpu in groups[llc_group_of(cpu)]


def test_distance_properties():
    c0 = core_of_stream(0)
    assert distance(c0, c0) == 0
    c1 = core_of_stream(1)
    assert distance(c0, c1) == distance(c1, c0)
    assert distance(c0, c1) in (0, 1, 2)


def test_lhq_topology_groups_schedule_correctly(param):
    """lhq with real LLC-derived groups still runs a pool to completion."""
    param("sched", "lhq")
    done = []
    p = ptg.PTGBuilder("lhq_topo", N=64)
    t = p.task("T", i=ptg.span(0, lambda g, l: g.N - 1))
    t.body(lambda es, task, g, l: done.append(l.i))
    with Context(nb_cores=4, scheduler="lhq") as ctx:
        ctx.add_taskpool(p.build())
        ctx.wait(timeout=60)
    assert sorted(done) == list(range(64))


# ---------------------------------------------------------------------------
# print_steals + alperf
# ---------------------------------------------------------------------------

def _sleepy_pool(n, delay=0.002):
    p = ptg.PTGBuilder("steals", N=n, D=delay)
    t = p.task("T", i=ptg.span(0, lambda g, l: g.N - 1))
    t.body(lambda es, task, g, l: time.sleep(g.D))
    return p.build()


def _fanout_tree(depth, delay=0.002):
    """Binary task tree: each completion releases two children into the
    completing worker's own queues — the shape that makes idle siblings
    STEAL (system-queue pops don't count; distance sentinel 99)."""
    p = ptg.PTGBuilder("tree", D=depth, S=delay)
    t = p.task("T",
               d=ptg.span(0, lambda g, l: g.D - 1),
               i=ptg.span(0, lambda g, l: (1 << l.d) - 1))
    f = t.flow("c", ptg.CTL)
    f.input(pred=("T", "c", lambda g, l: {"d": l.d - 1, "i": l.i // 2}),
            guard=lambda g, l: l.d > 0)
    f.output(succ=("T", "c",
                   lambda g, l: ({"d": l.d + 1, "i": 2 * l.i},
                                 {"d": l.d + 1, "i": 2 * l.i + 1})),
             guard=lambda g, l: l.d < g.D - 1)
    t.body(lambda es, task, g, l: time.sleep(g.S))
    return p.build()


def test_print_steals_counts(param):
    comp = repository.find("pins", "print_steals")
    mod = comp.open()
    try:
        before = sde.get("parsec::steals")
        with Context(nb_cores=4, scheduler="pbq") as ctx:
            ctx.add_taskpool(_fanout_tree(8))
            ctx.wait(timeout=60)
        assert sum(mod.steals.values()) > 0, \
            "no sibling steals observed with 4 workers on a fanout tree"
        assert sde.get("parsec::steals") > before
        assert sum(mod.distance.values()) >= sum(mod.steals.values())
    finally:
        comp.close(mod)


def test_alperf_samples_rate(param):
    param("pins_alperf_interval", 0.05)
    comp = repository.find("pins", "alperf")
    mod = comp.open()
    try:
        with Context(nb_cores=2) as ctx:
            ctx.add_taskpool(_sleepy_pool(120, delay=0.005))
            ctx.wait(timeout=60)
        time.sleep(0.12)           # at least one sample window
        assert mod.samples, "alperf never sampled"
        assert max(r for _, r in mod.samples) > 0
    finally:
        comp.close(mod)


# ---------------------------------------------------------------------------
# JDF unparser round-trip
# ---------------------------------------------------------------------------

def test_unparse_roundtrip_stencil(tmp_path):
    """parse -> unparse -> parse: the re-parsed template builds and runs
    to the same result as the original (jdf_unparse contract)."""
    import pathlib
    from parsec_tpu.data_dist.matrix import TwoDimBlockCyclic
    from parsec_tpu.models.stencil import stencil_reference

    src_path = (pathlib.Path(__file__).resolve().parent.parent
                / "examples" / "jdf" / "stencil_1D.jdf")
    jdf1 = ptg.load_jdf(src_path)
    text2 = ptg.unparse_jdf(jdf1)
    jdf2 = ptg.parse_jdf(text2, "stencil_rt")

    MB, NB, LMT, LNT, R, iters = 2, 8, 2, 3, 2, 4
    rng = np.random.default_rng(4)
    interior = rng.standard_normal((MB, LNT * (NB - 2 * R))).astype(
        np.float32)

    def run(jdf):
        def init(m, n, shape):
            tile = np.zeros(shape, np.float32)
            if m == 0:
                w = NB - 2 * R
                tile[:, R:NB - R] = interior[:, n * w:(n + 1) * w]
            return tile
        desc = TwoDimBlockCyclic("descA", lm=LMT * MB, ln=LNT * NB,
                                 mb=MB, nb=NB, P=1, Q=1, init_fn=init)
        W = np.array([0.1, 0.2, 0.4, 0.2, 0.1])
        tp = jdf.build(descA=desc, iter=iters, R=R, W=W, LMT=LMT, LNT=LNT)
        with Context(nb_cores=0) as ctx:
            ctx.add_taskpool(tp)
            ctx.wait(timeout=120)
        m = iters % LMT
        return np.concatenate(
            [np.asarray(desc.data_of(m, n).newest_copy().value)[:, R:NB - R]
             for n in range(LNT)], axis=1)

    got1, got2 = run(jdf1), run(jdf2)
    np.testing.assert_allclose(got1, got2, rtol=0, atol=0)
    want = np.stack([stencil_reference(row, np.array([0.1, 0.2, 0.4, 0.2,
                                                      0.1]), iters)
                     for row in interior])
    np.testing.assert_allclose(got1, want, rtol=1e-4, atol=1e-5)


def test_unparse_preserves_ud_surface():
    """%option, task props, SIMCOST, ranged arrows, dep [type=] props and
    NULL targets survive the round trip structurally."""
    src = """
%option termdet = local
V [type = data]
T(i) [make_key_fn = mk]
  i = 0 .. 3
  j = i * 2
  : V(0)
  SIMCOST i + 1
  READ X <- (i > 0) ? X T(i-1) : NULL
  CTL c <- c S(0 .. 3)
BODY
  pass
END
S(k)
  k = 0 .. 3
  : V(0)
  CTL c -> c T(0 .. 3)
BODY
  pass
END
"""
    jdf1 = ptg.parse_jdf(src, "ud")
    jdf2 = ptg.parse_jdf(ptg.unparse_jdf(jdf1), "ud2")
    assert jdf2.options == jdf1.options
    t1, t2 = jdf1.tasks["T"], jdf2.tasks["T"]
    assert t2.props == t1.props
    assert t2.simcost_src == t1.simcost_src
    assert t2.derived == t1.derived
    assert t2.ranges == t1.ranges
    for f1, f2 in zip(t1.flows, t2.flows):
        assert f2.name == f1.name and f2.access == f1.access
        assert len(f2.arrows) == len(f1.arrows)
        for a1, a2 in zip(f1.arrows, f2.arrows):
            assert a2.direction == a1.direction
            assert a2.then_tgt == a1.then_tgt
            assert a2.else_tgt == a1.else_tgt
            assert (a2.guard_src or "").replace(" ", "") == \
                (a1.guard_src or "").replace(" ", "")
