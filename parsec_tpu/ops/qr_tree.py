"""The reduction tree of a hierarchical tile QR: who kills whom, panel by
panel.

DPLASMA's ``dplasma_qrtree_t`` (``dplasma_hqr_init``; since then libhqr;
Dongarra, Faverge, Herault, Jacquelin, Langou, Robert, "Hierarchical QR
factorization algorithms for multi-core clusters", Parallel Computing 39,
2013), cut to what one process uses: TS domains of ``a`` tile rows and a
binary TT tree over the domain heads; no high-level (inter-process) tree,
no domino.  The PTG of :mod:`parsec_tpu.ops.qr` is a function of this
object alone: ``getnbgeqrf``, ``getm``, ``geti``, ``gettype``,
``currpiv``, ``nextpiv`` and ``prevpiv`` are the reference's interface,
argument for argument, with ``mt`` as its "none".

In panel ``k`` (``0 .. nt-1``) the rows are ``m = k .. mt-1``:

* row ``m`` belongs to domain ``m // a``, by GLOBAL row index: a row's
  head does not move from panel to panel.  The head of a domain is its
  first row present, so the domain that holds row ``k`` starts at ``k``;
* every head gets a ``geqrt`` (type 1); inside a domain the head kills
  the other rows (type 0, TS: a triangle on top of a square) one after
  another in row order;
* the heads, numbered ``j = 0 .. H-1`` from the top, are reduced by a
  binary tree (TT: triangle on triangle): at level ``l`` the head with
  ``j mod 2^(l+1) = 2^l`` is killed by head ``j - 2^l``, after its own
  domain and its own earlier kills.  The root is row ``k``.

With ``a >= mt`` there is one domain and no TT level: the flat tree, the
chain of the classic tile QR.

Plain Python and numpy, no JAX.  The tables (``nt x mt`` small integers)
are built at the first question, under the span ``attach:tree``: an
executor that binds a stored attach plan asks none.
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

from ..profiling import pins


class QRTree:
    """TS domains of ``a`` rows under a binary TT tree, for an ``mt x nt``
    grid of tiles (``mt >= nt``)."""

    KIND = "ts-domains/binary-tt"

    def __init__(self, mt: int, nt: int, a: int):
        mt, nt, a = int(mt), int(nt), int(a)
        if not (mt >= nt >= 1 and a >= 1):
            raise ValueError(f"QRTree needs mt >= nt >= 1 and a >= 1; got "
                             f"mt={mt}, nt={nt}, a={a}")
        # (every a >= mt is the one flat tree: one fingerprint for it)
        self.mt, self.nt, self.a = mt, nt, min(a, mt)
        self._t = None

    def plan_fingerprint(self) -> Tuple:
        """What the DAG is a function of (``dsl/attach_plan.py``)."""
        return ("QRTree", self.KIND, self.a, self.mt, self.nt)

    def __repr__(self) -> str:
        return f"QRTree(mt={self.mt}, nt={self.nt}, a={self.a})"

    # -- the tables -------------------------------------------------------
    def _tables(self):
        if self._t is None:
            with pins.span("attach:tree", mt=self.mt, nt=self.nt, a=self.a):
                self._t = self._build()
        return self._t

    def _build(self):
        mt, nt, a = self.mt, self.nt, self.a
        none = mt
        shape = (nt, mt + 1)    # (column mt: the answers for "none")
        typ = np.zeros(shape, np.int32)
        piv = np.full(shape, none, np.int32)     # who kills m
        nxt = np.full(shape, none, np.int32)     # the killer's next victim
        prv = np.full(shape, none, np.int32)     # ... and the one before
        first = np.full(shape, none, np.int32)   # p's first victim
        last = np.full(shape, none, np.int32)    # ... and its last
        level = np.zeros(shape, np.int32)        # the step m is killed at
        gi = np.full(shape, -1, np.int32)        # m's index among the heads
        ki = np.full(shape, -1, np.int32)        # ... among the TS / TT kills
        heads_of, ts_of, tt_of = [], [], []
        for k in range(nt):
            heads = [k] + list(range((k // a + 1) * a, mt, a))
            kills = {p: [] for p in heads}       # in the order p makes them
            busy = {}                            # p's step after its kills
            for j, p in enumerate(heads):
                top = heads[j + 1] if j + 1 < len(heads) else mt
                kills[p] = list(range(p + 1, top))
                busy[p] = len(kills[p])
            step = 1
            while step < len(heads):
                for j in range(step, len(heads), 2 * step):
                    p, m = heads[j - step], heads[j]
                    kills[p].append(m)
                    # after both sides' earlier kills
                    busy[p] = level[k, m] = max(busy[p], busy[m]) + 1
                step *= 2
            typ[k, heads] = 1
            ts, tt = [], []
            for p in heads:
                for i, m in enumerate(kills[p]):
                    piv[k, m] = p
                    if not typ[k, m]:
                        level[k, m] = i + 1
                        ts.append(m)
                    else:
                        tt.append(m)
                    prv[k, m] = kills[p][i - 1] if i else none
                    nxt[k, m] = kills[p][i + 1] if i + 1 < len(kills[p]) \
                        else none
                if kills[p]:
                    first[k, p], last[k, p] = kills[p][0], kills[p][-1]
            ts.sort()
            tt.sort()
            gi[k, heads] = np.arange(len(heads))
            for rows in (ts, tt):
                ki[k, rows] = np.arange(len(rows))
            heads_of.append(heads)
            ts_of.append(ts)
            tt_of.append(tt)
        # plain lists: an int of a nested list is what ``eval`` hands on
        # cheapest (a numpy scalar would ride every task as a value)
        return {"type": typ.tolist(), "piv": piv.tolist(),
                "next": nxt.tolist(), "prev": prv.tolist(),
                "first": first.tolist(), "last": last.tolist(),
                "level": level.tolist(), "gi": gi.tolist(), "ki": ki.tolist(),
                "heads": heads_of, "kills": (ts_of, tt_of)}

    # -- dplasma_qrtree_t -------------------------------------------------
    def getnbgeqrf(self, k: int) -> int:
        """The number of ``geqrt`` of panel ``k``: its domain heads."""
        return len(self._tables()["heads"][k])

    def getm(self, k: int, i: int) -> int:
        """The row of the ``i``-th ``geqrt`` of panel ``k``."""
        return self._tables()["heads"][k][i]

    def geti(self, k: int, m: int) -> int:
        """The inverse of :meth:`getm`: the index of head ``m``."""
        return self._tables()["gi"][k][m]

    def gettype(self, k: int, m: int) -> int:
        """0: row ``m`` is killed as a square (TS); 1: it gets a
        ``geqrt`` and, but for row ``k``, is killed as a triangle (TT)."""
        return self._tables()["type"][k][m]

    def currpiv(self, k: int, m: int) -> int:
        """The row that kills ``m`` in panel ``k`` (``mt`` for row k)."""
        return self._tables()["piv"][k][m]

    def nextpiv(self, k: int, p: int, start: int) -> int:
        """The row ``p`` kills after ``start``; its first with ``start ==
        mt``; ``mt`` when there is none."""
        t = self._tables()
        return t["first"][k][p] if start == self.mt else t["next"][k][start]

    def prevpiv(self, k: int, p: int, start: int) -> int:
        """The row ``p`` killed before ``start``; its last with ``start
        == p``; ``mt`` when there is none."""
        t = self._tables()
        return t["last"][k][p] if start == p else t["prev"][k][start]

    # -- beside it: the two classes of kills as index sets ----------------
    def getnbkill(self, k: int, tt: int) -> int:
        """The number of TS (``tt`` = 0) or TT (1) kills of panel ``k``."""
        return len(self._tables()["kills"][tt][k])

    def getmkill(self, k: int, tt: int, i: int) -> int:
        """The row of the ``i``-th TS / TT kill of panel ``k``, in row
        order."""
        return self._tables()["kills"][tt][k][i]

    def getikill(self, k: int, m: int) -> int:
        """The inverse of :meth:`getmkill`, within ``m``'s own class."""
        return self._tables()["ki"][k][m]

    def level(self, k: int, m: int) -> int:
        """The step of panel ``k``'s reduction at which ``m`` is killed
        (1 for the first victim of a head; a flat tree: ``m - k``)."""
        return self._tables()["level"][k][m]


def flat_tree(mt: int, nt: int) -> QRTree:
    """One domain, no TT level: row ``k`` kills ``k+1 .. mt-1`` in order."""
    return QRTree(mt, nt, mt)
