"""Tasks, operations and classes of the tile inverse of a symmetric
positive definite matrix (``dpotrf``, ``dtrtri``, ``dlauum`` one after
another, lower storage) on nt x nt tiles, from shapes alone.  Imports
nothing of the program.

Each of the three steps is N^3 / 3 operations (LAPACK's counts, lower
order terms dropped: potrf N^3/3, trtri N^3/3, lauum N^3/3) and each tile
DAG has dpotrf's task count: nt tasks on the diagonal, nt (nt - 1) on a
panel or a row, nt (nt - 1) (nt - 2) / 6 gemm updates."""

from benchmark import ops_count

#: the three DAGs' task classes, as the device programs' module names
#: carry them (``benchmark/trace/modules.py``)
POTRF_CLASSES = ("potrf", "trsm", "syrk", "gemm")
TRTRI_CLASSES = ("trtri_trsm_r", "trtri_gemm", "trtri_trsm_l", "trtri_diag")
LAUUM_CLASSES = ("lauum_syrk", "lauum_gemm", "lauum_trmm", "lauum_diag")
CLASSES = POTRF_CLASSES + TRTRI_CLASSES + LAUUM_CLASSES

MEMBERS = 3


def member_flops(n: int) -> float:
    """One step of the three: n^3 / 3."""
    return ops_count.dpotrf_flops(n)


def poinv_flops(n: int) -> float:
    """potrf + trtri + lauum: n^3."""
    return MEMBERS * member_flops(n)


def member_ntasks(nt: int) -> int:
    return ops_count.dpotrf_ntasks(nt)


def poinv_ntasks(nt: int) -> int:
    return MEMBERS * member_ntasks(nt)
