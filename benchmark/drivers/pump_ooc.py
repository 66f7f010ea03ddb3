"""Out-of-core tile dpotrf through ``NativeExecutor(native_device=True)``:
``pump.py``'s driver, calling sequence and clocks as they are, for a
matrix that is larger than the device's budget.

It adds two things.  A refusal: a program whose device module does not
declare that it runs a pool larger than its budget in bounded host
memory (``parsec_tpu.device.residency.OUT_OF_CORE``) cannot run the
configuration.  The program from before it kept a host value cached
beside every resident tile that went home, and a landing copy of it: on
the chip the machine ended it for want of HOST memory (48.6 GB of 40
GiB) 274 s into its first solve, 7,491 of 15,180 tasks done (my chip
run, PR 30).  A run that the machine kills tells a check nothing, so the
cell refuses such a program at once instead.  And the guarantees of an
out-of-core solve that ``pump.py`` has no place for: the counters that
say eviction or a copy home took a way around (below) are held at 0 with
the other fallback counters.
"""

from __future__ import annotations

from benchmark import harness
from benchmark.drivers import pump
from parsec_tpu.device import residency

if not getattr(residency, "OUT_OF_CORE", False):
    raise harness.BenchError(
        "spotrf_tile_nb2048_ooc_1chip: this program's device module does "
        "not declare parsec_tpu.device.residency.OUT_OF_CORE (a pool "
        "larger than the device's budget in bounded host memory); with "
        "this matrix it runs the host out of memory mid-solve")

#: what must stay 0 beside ``_common._DEVICE_FALLBACKS``: a copy home
#: that left a cached host value or took a landing copy, room that could
#: not be made under the budget, a tile resident and charged to nobody
_OOC_FALLBACKS = ("wb_alias_fallbacks", "reserve_gave_up",
                  "unaccounted_tiles")


def open(config, traffic, options, devices, platform):
    return PumpOoc(options, platform)


class PumpOoc(pump.Pump):
    def counters(self):
        out = super().counters()
        if self.dev is not None:
            out["fallbacks"] += sum(self.dev.stats.get(k, 0)
                                    for k in _OOC_FALLBACKS)
        return out
