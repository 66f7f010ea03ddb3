"""Graph-synthesis invariants: generated programs verify clean, fuse-
hintable elementwise chains, reader/private-copy insertion rules, the
shared check_tiling validator, and the shared redistribute-algo
resolver."""

import numpy as np
import pytest

from parsec_tpu import array as pa
from parsec_tpu.analysis import verify_ptg
from parsec_tpu.analysis.findings import errors_of
from parsec_tpu.ops.tiles import check_tiling


@pytest.mark.parametrize("which", ["mixed", "chain", "dist"])
def test_canonical_programs_verify_clean(which):
    """The acceptance gate: generated graphs pass PTG.verify with zero
    findings (reciprocity, hazards, liveness, expression lint)."""
    prog = pa.canonical_program(which)
    assert prog.verify() == []


def test_elementwise_chain_is_ptg060_fusible():
    """Elementwise chains are the canonical fusible-chain case: the
    advisory lint must flag them, and --strict must not fail on it."""
    prog = pa.canonical_program("chain")
    findings = verify_ptg(prog.ptg, prog.constants, fusion_hints=True)
    assert findings and not errors_of(findings)
    assert any(f.code == "PTG060" for f in findings)


def test_all_classes_carry_array_prefix():
    """Every generated class is ``arr_*`` so the critpath per_label
    rollup groups the whole program under one ``array`` row."""
    from parsec_tpu.profiling.critpath import label_of

    prog = pa.canonical_program("mixed")
    assert prog.ptg.classes
    for name in prog.ptg.classes:
        assert label_of(name) == "array", name


def test_single_rank_has_no_readers_distributed_does():
    """Forwarding reader classes exist exactly when a source tile may be
    read away from its owner: never on one rank, on unaligned
    distributed reads otherwise."""
    single = pa.canonical_program("mixed")
    assert not [c for c in single.ptg.classes if c.startswith("arr_ld")]
    dist = pa.canonical_program("dist")
    assert [c for c in dist.ptg.classes if c.startswith("arr_ld")]


def test_private_copy_only_when_needed():
    """Cholesky scribbles on its entry tiles: a leaf input gets the
    arr_cp private-copy class; a single-consumer elementwise producer
    feeds the factorization directly (no materialize-and-reload, no
    copy)."""
    G = np.eye(12) * 12.0
    # chol(leaf): the leaf must survive -> copy class
    A = pa.from_numpy(G, 4)
    p1 = pa.lower([A.cholesky()], use_tpu=False)
    assert any(c.startswith("arr_cp") for c in p1.ptg.classes)
    # chol(sole-consumer ew): entry tiles are already private
    B = pa.from_numpy(G, 4)
    Z = pa.from_numpy(np.zeros((12, 12)), 4)
    p2 = pa.lower([(B + Z).cholesky()], use_tpu=False)
    assert not any(c.startswith("arr_cp") for c in p2.ptg.classes)
    assert p2.verify() == []
    # ...but a MATERIALIZED producer must not be scribbled on
    C = pa.from_numpy(G, 4)
    m = C + Z
    p3 = pa.lower([m.cholesky(), m], use_tpu=False)
    assert any(c.startswith("arr_cp") for c in p3.ptg.classes)
    assert p3.verify() == []


def test_cholesky_input_survives():
    """cholesky(M) must not destroy M (the classic in-place trap)."""
    from parsec_tpu import Context

    rng = np.random.default_rng(41)
    G = rng.standard_normal((12, 12))
    spd = G @ G.T + 12 * np.eye(12)
    A = pa.from_numpy(spd, 4)
    C = A.cholesky()
    with Context(nb_cores=2) as ctx:
        C.compute(ctx, use_tpu=False)
    assert np.array_equal(A.to_numpy(), spd), "input was mutated"
    assert np.allclose(np.tril(C.to_numpy()), np.linalg.cholesky(spd))


def test_solve_row_aligned_leaf_L_needs_no_readers():
    """solve(L_leaf, b) on a row-only (q=1) grid reads L owner-locally
    (L's row i and the rhs row i share an owner) — no forwarding
    readers; a 2-D (q>1) grid DOES need them."""
    L = np.tril(np.ones((16, 16))) + 16 * np.eye(16)
    rhs = np.ones((16, 2))
    for q, want_readers in ((1, False), (2, True)):
        dist = pa.BlockCyclic(2, 1) if q == 1 else pa.BlockCyclic(1, 2)
        Ld = pa.from_numpy(L, 4, dist=dist, myrank=0)
        bd = pa.from_numpy(rhs, 4, 2, dist=dist, myrank=0)
        prog = pa.lower([Ld.solve(bd)], use_tpu=False)
        readers = [c for c in prog.ptg.classes if c.startswith("arr_ld")]
        assert bool(readers) == want_readers, (q, readers)
        assert prog.verify() == []


def test_scalar_ops_and_lazy_zeros():
    A = pa.from_numpy(np.ones((8, 8)), 4)
    with pytest.raises(TypeError, match="scalar"):
        A + 1.0
    with pytest.raises(TypeError, match="scalar"):
        A - 1.0
    # zeros() never builds a dense array: tiles materialize lazily
    Z = pa.zeros((8, 8), 4)
    assert Z.computed and Z._node.coll.materialized_keys() == []
    with pytest.raises(ValueError, match="eager datadist path"):
        # same-geometry redistribute is a lazy copy: explicit eager-path
        # arguments must not be silently dropped
        A.redistribute(pa.BlockCyclic(1, 1), algo="coll")


def test_shape_and_tiling_validation():
    A = pa.from_numpy(np.zeros((8, 8)), 4)
    B = pa.from_numpy(np.zeros((8, 8)), 2)
    with pytest.raises(ValueError, match="tilings"):
        A + B
    with pytest.raises(ValueError, match="inner"):
        A @ pa.from_numpy(np.zeros((4, 8)), 4)
    with pytest.raises(ValueError, match="square"):
        pa.from_numpy(np.zeros((8, 4)), 4).cholesky()
    with pytest.raises(ValueError, match="mixes rank grids"):
        a2 = pa.from_numpy(np.zeros((8, 8)), 4, dist=pa.Block1D(2))
        a4 = pa.from_numpy(np.zeros((8, 8)), 4, dist=pa.Block1D(4))
        pa.lower([a2 + a4])


# ---------------------------------------------------------------------------
# shared tiling validator (satellite)
# ---------------------------------------------------------------------------

def test_check_tiling_contract():
    assert check_tiling(16, 4) == 4
    assert check_tiling(20, 8, allow_ragged=True) == 3
    with pytest.raises(ValueError, match="not divisible"):
        check_tiling(20, 8)
    with pytest.raises(ValueError, match="positive"):
        check_tiling(16, 0)
    with pytest.raises(ValueError, match="positive"):
        check_tiling(-4, 2)


def test_segmented_builders_reject_readably():
    from parsec_tpu.ops.segmented_chol import segmented_cholesky_ptg
    from parsec_tpu.ops.segmented_lu import segmented_lu_ptg
    from parsec_tpu.ops.segmented_qr import segmented_qr_ptg

    for builder, what in ((segmented_cholesky_ptg, "cholesky"),
                          (segmented_lu_ptg, "LU"),
                          (segmented_qr_ptg, "QR")):
        with pytest.raises(ValueError, match="not divisible"):
            builder(100, 48)


def test_stencil_grid_raises_instead_of_truncating():
    """A non-dividing stencil grid used to be a bare assert (silent
    truncation under -O) — now the shared readable error."""
    from parsec_tpu.ops.stencil import stencil_grid

    with pytest.raises(ValueError, match="stencil.*not divisible"):
        stencil_grid(np.zeros((9, 8)), 2, 2)
    # dividing grids still construct
    b = stencil_grid(np.zeros((8, 8)), 2, 2)
    assert (b.mb, b.nb) == (4, 4)


# ---------------------------------------------------------------------------
# shared redistribute-algo resolver (satellite)
# ---------------------------------------------------------------------------

def test_redistribute_algo_resolver_precedence():
    from parsec_tpu.datadist.redistribute import resolve_redistribute_algo
    from parsec_tpu.utils import mca_param

    # default: auto resolves by mesh shape (no context -> dtd)
    assert resolve_redistribute_algo(None, None) == "dtd"
    assert resolve_redistribute_algo("auto", None) == "dtd"
    assert resolve_redistribute_algo("coll", None) == "coll"
    # an explicitly configured MCA value beats a caller's literal "auto"
    mca_param.params.set("runtime", "redistribute_algo", "coll")
    try:
        assert resolve_redistribute_algo("auto", None) == "coll"
        assert resolve_redistribute_algo(None, None) == "coll"
        # ...but never an explicit caller choice
        assert resolve_redistribute_algo("dtd", None) == "dtd"
    finally:
        mca_param.params.unset("runtime", "redistribute_algo")
    with pytest.raises(ValueError, match="unknown redistribute algo"):
        resolve_redistribute_algo("bogus", None)
