#!/usr/bin/env python3
"""`chip_control.py` with the gang configuration's control beside the others:

    python3 benchmarks/tests/chip_control_gang.py --workload gang-5k.backlog \
        --control ignore_gangs --seeds 11 --seconds 40

`ignore_gangs` changes which program the engine compiles (the gang-free one),
so it is in place before the warm-up, as `lower_commit_precision` is: the
window then holds no compile and `gangs_partly_bound` fails alone.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

if __name__ == "__main__":
    from benchmarks.tests import chip_control, controls, controls_gang

    controls.CONTROLS.update(controls_gang.CONTROLS)
    if "ignore_gangs" in sys.argv:
        controls_gang.ignore_gangs(None, None)
    sys.exit(chip_control.main())
