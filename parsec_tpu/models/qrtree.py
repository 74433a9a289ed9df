"""The reduction tree of a hierarchical tile QR: which row kills which, in
what order, at every panel step (DPLASMA's ``dplasma_qrtree_t``, as
``dplasma_hqr_init`` builds it for one node).

At step k the rows k .. MT-1 are cut into *domains* of ``a`` consecutive
rows counted from row k (the last may be shorter).  Each domain's first row,
its *head*, is factored by GEQRT; the other rows of the domain are killed
onto the head by TSQRT in increasing row order (a TS chain of at most a - 1
links).  The heads are then reduced onto row k by TTQRT kills over a
low-level tree:

- ``binary``: at level l the head of rank i (among the heads, in row order),
  where i mod 2^(l+1) = 0, kills the head of rank i + 2^l if it exists;
- ``flat``: the head of rank 0 kills the others in rank order.

Every row but k is killed exactly once a step, and a killer performs its
kills in order: its domain's TS kills, then its TT kills level by level.
With ``a >= MT`` there is one domain and the tree is the flat tree of
``zgeqrf.jdf`` (``qr.py:tiled_qr_ptg``).

The answers are DPLASMA's questions under plain names: ``is_head`` and the
heads (``getnbgeqrf`` / ``getm`` / ``geti``), ``kind`` (``gettype``),
``killer`` (``currpiv``), ``prev_kill`` / ``next_kill`` (``prevpiv`` /
``nextpiv``), and ``kills`` / ``last_kill`` for a killer's whole sequence.
The tables are built once, at the first question, under the phase span
``ptg.qrtree`` (``tiled_hqr_ptg`` asks when its pool is enqueued); every
later answer is a lookup.
"""

from __future__ import annotations

import functools

from ..prof import spans

TS, TT = 1, 2           # how a row is killed (``kind``); 0: the survivor


class QRTree:
    """The hierarchical tree over an ``mt`` x ``nt`` tile grid (mt >= nt),
    domains of ``a`` rows, low-level tree ``low`` ("binary" or "flat")."""

    LOWS = ("binary", "flat")

    def __init__(self, mt: int, nt: int, a: int, low: str = "binary") -> None:
        if not 1 <= nt <= mt:
            raise ValueError(f"a tall or square tile grid: mt={mt}, nt={nt}")
        if a < 1:
            raise ValueError(f"domains of a >= 1 rows, not {a}")
        if low not in self.LOWS:
            raise ValueError(f"low-level tree {low!r} is not one of "
                             f"{self.LOWS}")
        self.mt, self.nt, self.a, self.low = mt, nt, a, low
        self.kt = nt            # panel steps

    def __repr__(self) -> str:
        return (f"QRTree(mt={self.mt}, nt={self.nt}, a={self.a}, "
                f"low={self.low!r})")

    # ------------------------------------------------------------ the plan
    def _tt_kills(self, heads: list[int]) -> dict[int, list[int]]:
        """Killer head -> the heads it kills, in order."""
        h = len(heads)
        out: dict[int, list[int]] = {p: [] for p in heads}
        if self.low == "flat":
            out[heads[0]] = heads[1:]
            return out
        for i in range(h):
            level = 1
            while i % (2 * level) == 0 and i + level < h:
                out[heads[i]].append(heads[i + level])
                level *= 2
        return out

    @functools.cached_property
    def _plan(self) -> tuple:
        """Per step, lists indexed by row: kind, killer, previous and next
        kill of the killer, the row's own kills (first, last), head-ness;
        per step the heads, TS-killed and TT-killed rows; the panel's
        critical path summed over the steps."""
        with spans.phase("ptg.qrtree"):
            mt = self.mt
            kind, killer, prev, nxt, kills, head = [], [], [], [], [], []
            heads_of, ts_of, tt_of = [], [], []
            levels = 0
            for k in range(self.kt):
                kd, kl, pv, nx = [0] * mt, [None] * mt, [None] * mt, \
                    [None] * mt
                ks: list[tuple] = [()] * mt
                hd = [False] * mt
                heads = list(range(k, mt, self.a))
                order: dict[int, list[int]] = {}
                for p in heads:
                    hd[p] = True
                    order[p] = list(range(p + 1, min(p + self.a, mt)))
                    for m in order[p]:
                        kd[m] = TS
                for p, killed in self._tt_kills(heads).items():
                    order[p] += killed
                    for m in killed:
                        kd[m] = TT
                # the panel's kernels as a DAG: a row's tile is ready one
                # kernel after both its killer's and its own were
                ready = [0] * mt
                for p in heads:
                    ready[p] = 1                        # GEQRT
                # a head kills only heads of a higher rank: the later
                # ranks' sequences are walked first
                for p in reversed(heads):
                    seq = order[p]
                    ks[p] = tuple(seq)
                    for i, m in enumerate(seq):
                        kl[m] = p
                        pv[m] = seq[i - 1] if i else None
                        nx[m] = seq[i + 1] if i + 1 < len(seq) else None
                        ready[p] = max(ready[p], ready[m]) + 1
                levels += ready[k]
                kind.append(kd)
                killer.append(kl)
                prev.append(pv)
                nxt.append(nx)
                kills.append(ks)
                head.append(hd)
                heads_of.append(tuple(heads))
                ts_of.append(tuple(m for m in range(k, mt) if kd[m] == TS))
                tt_of.append(tuple(m for m in range(k, mt) if kd[m] == TT))
            return (kind, killer, prev, nxt, kills, head, heads_of, ts_of,
                    tt_of, levels)

    # ------------------------------------------------------- the questions
    def heads(self, k: int) -> tuple:
        """The heads of step k in rank order (``getm(k, i)``)."""
        return self._plan[6][k]

    def ts_rows(self, k: int) -> tuple:
        """The rows a TSQRT kills at step k."""
        return self._plan[7][k]

    def tt_rows(self, k: int) -> tuple:
        """The heads a TTQRT kills at step k."""
        return self._plan[8][k]

    def is_head(self, k: int, m: int) -> bool:
        return self._plan[5][k][m]

    def kind(self, k: int, m: int) -> int:
        """``TS`` or ``TT``: how row m is killed at step k; 0 for row k and
        for the rows above it."""
        return self._plan[0][k][m]

    def killer(self, k: int, m: int) -> int | None:
        return self._plan[1][k][m]

    def prev_kill(self, k: int, m: int) -> int | None:
        """The row m's killer kills just before m at step k; None where m is
        its first (the killer's tile comes from its GEQRT)."""
        return self._plan[2][k][m]

    def next_kill(self, k: int, m: int) -> int | None:
        """The row m's killer kills just after m; None where m is its last."""
        return self._plan[3][k][m]

    def kills(self, k: int, p: int) -> tuple:
        """The rows p kills at step k, in order (empty for a row that is not
        a head)."""
        return self._plan[4][k][p]

    def first_kill(self, k: int, p: int) -> int | None:
        seq = self._plan[4][k][p]
        return seq[0] if seq else None

    def last_kill(self, k: int, p: int) -> int | None:
        seq = self._plan[4][k][p]
        return seq[-1] if seq else None

    @property
    def panel_levels(self) -> int:
        """The panel's critical path in kernels, summed over the steps:
        GEQRT, then the longest chain of kills that ends on row k."""
        return self._plan[9]
