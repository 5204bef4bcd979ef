"""Device: the share of the Moonlight window in which no operation ran on
the chip (%, device trace busy union) -- ``lm.device_idle``'s reading,
under its own name because in this cell it moves ``itl_p95_ms``."""

import os

from bench import harness

read = harness.load_module(os.path.join(harness.BENCH, "metrics",
                                        "lm.device_idle.py")).read
