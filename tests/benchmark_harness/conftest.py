"""``bench_testlib.TINY`` names the tiny traffic of each driver the
benchmark had when it was written; a driver added since registers its
own here, so that ``tiny_spec()`` finds every cell's rehearsal."""

import bench_testlib

bench_testlib.TINY.setdefault("pump_geqrf", "tiny_pump_geqrf")
