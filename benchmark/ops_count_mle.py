"""Tasks, operations and bytes of one mixed-precision likelihood
evaluation on an n x n covariance matrix cut into nb x nb tiles, from
shapes and the band alone (tile (m, n) is float32 where m - n < band_f32,
bfloat16 elsewhere)."""

#: the DAG's task classes, as the device programs' module names carry them
CLASSES = ("dcmg", "potrf", "trsm", "syrk", "gemm", "convert", "trsv",
           "gemv", "logdet", "dot")


def lower_tiles(nt: int) -> int:
    return nt * (nt + 1) // 2


def f32_tiles(nt: int, band_f32: int) -> int:
    """Lower tiles the band rule stores in float32."""
    return sum(nt - d for d in range(min(band_f32, nt)))


def matrix_bytes(n: int, nb: int, band_f32: int) -> int:
    """The matrix as the precision map stores it: what ``dcmg`` writes
    and what stays resident."""
    nt = n // nb
    hi = f32_tiles(nt, band_f32)
    return (4 * hi + 2 * (lower_tiles(nt) - hi)) * nb * nb


def converted_tiles(nt: int, band_f32: int) -> int:
    """Float32 panel tiles L(n, k), 0 < n - k < band_f32, that a gemm
    writing a bfloat16 tile reads: those with a row m >= n + band_f32."""
    return sum(1 for k in range(nt) for n in range(k + 1, nt)
               if n - k < band_f32 and n + band_f32 <= nt - 1)


def ntasks(nt: int, band_f32: int) -> int:
    """dcmg + the four dpotrf classes + convert + trsv + gemv + the two
    reductions."""
    dpotrf = nt + nt * (nt - 1) + nt * (nt - 1) * (nt - 2) // 6
    return (lower_tiles(nt) + dpotrf + converted_tiles(nt, band_f32)
            + nt + nt * (nt - 1) // 2 + 2 * nt)


def update_flops(nt: int, nb: int) -> float:
    """The syrk and gemm tasks' mathematical operations: nb^3 a syrk (a
    symmetric update), 2 nb^3 a gemm, whatever implements them."""
    syrk = nt * (nt - 1) // 2
    gemm = nt * (nt - 1) * (nt - 2) // 6
    return float(syrk + 2 * gemm) * float(nb) ** 3


def input_bytes(n: int) -> int:
    """What a solve stages in: the locations (n x 2 float32), the
    observations, theta (3) and the two zeroed reductions (2 each)."""
    return 4 * (2 * n + n + 3 + 4)


def result_bytes(n: int) -> int:
    """What a solve brings home: y and the two reductions."""
    return 4 * (n + 4)
