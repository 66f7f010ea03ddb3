"""Data collections library (reference L6, ``parsec/data_dist/``)."""

from .matrix import (
    FULL,
    LOWER,
    UPPER,
    SymTwoDimBlockCyclic,
    SymTwoDimBlockCyclicBand,
    TwoDimBlockCyclicBand,
    TiledMatrix,
    TwoDimBlockCyclic,
    TwoDimTabular,
    VectorTwoDimCyclic,
    advise_data_on_devices,
)
from .ops import apply_taskpool, map_operator, reduce_cols, reduce_rows, reduce_taskpool
from .redistribute import redistribute

__all__ = [
    "FULL",
    "LOWER",
    "UPPER",
    "TiledMatrix",
    "TwoDimBlockCyclic",
    "SymTwoDimBlockCyclic",
    "SymTwoDimBlockCyclicBand",
    "TwoDimBlockCyclicBand",
    "TwoDimTabular",
    "VectorTwoDimCyclic",
    "advise_data_on_devices",
    "apply_taskpool",
    "map_operator",
    "reduce_taskpool",
    "reduce_rows",
    "reduce_cols",
    "redistribute",
]
