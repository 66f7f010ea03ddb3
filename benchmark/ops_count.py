"""Operations and bytes of the mathematics, from shapes alone."""


def dpotrf_flops(n: int) -> float:
    """Cholesky factorization of an n x n matrix: n^3 / 3 (LAPACK's
    count, lower-order terms dropped)."""
    return float(n) ** 3 / 3.0


def dpotrf_ntasks(nt: int) -> int:
    """Tasks of the tile algorithm on nt x nt tiles: potrf + trsm + syrk
    + gemm."""
    return nt + nt * (nt - 1) + nt * (nt - 1) * (nt - 2) // 6


def lower_tiles_bytes(n: int, nb: int, itemsize: int = 4) -> int:
    """Bytes of the factor as lower tiles: what one solve has to bring
    home once."""
    nt = n // nb
    return nt * (nt + 1) // 2 * nb * nb * itemsize


def roofline_pct(flops: float, peak_flops_per_s: float, chips: int,
                 busy_s: float) -> float:
    """The least time ``chips`` chips could take for ``flops`` operations
    at their published peak, over the seconds an operation ran on a chip
    (averaged over the chips), in percent."""
    return 100.0 * flops / (peak_flops_per_s * chips) / busy_s
