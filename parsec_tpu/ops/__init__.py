"""Compute bodies (tile kernels) and flagship taskpools."""

from . import tiles
from .attention import (
    attention_task_count,
    build_flash_attention,
    flash_attention_ptg,
    ring_attention_ptg,
    ring_attention_builder,
    run_flash_attention,
    run_flash_attention_native,
    run_ring_attention_graph,
)
from .cholesky import cholesky_dtd, cholesky_ptg, run_cholesky
from .lu import lu_ptg, run_lu
from .panel_chol import PanelCholesky, WholeCholesky
from .inverse import lauum_ptg, poinv, trtri_ptg
from .segmented_chol import SegmentedCholesky, segmented_cholesky_ptg
from .segmented_lu import SegmentedLU, segmented_lu_ptg
from .segmented_qr import SegmentedQR, segmented_qr_ptg
from .qr import qr_ptg, run_qr
from .qr_tree import QRTree, flat_tree

__all__ = ["tiles", "cholesky_ptg", "cholesky_dtd", "run_cholesky", "lu_ptg", "run_lu",
           "flash_attention_ptg", "ring_attention_ptg",
           "build_flash_attention", "run_flash_attention",
           "run_flash_attention_native", "run_ring_attention_graph",
           "ring_attention_builder", "attention_task_count",
           "PanelCholesky", "WholeCholesky",
           "SegmentedCholesky", "segmented_cholesky_ptg",
           "SegmentedLU", "segmented_lu_ptg",
           "SegmentedQR", "segmented_qr_ptg",
           "qr_ptg", "run_qr", "QRTree", "flat_tree",
           "trtri_ptg", "lauum_ptg", "poinv"]
