#!/usr/bin/env python3
"""`chip_control.py` with the extender configuration's controls beside the
others:

    python3 benchmarks/tests/chip_control_extender.py \
        --workload extender-5k.filter-prioritize \
        --control no_assume_on_bind --seeds 11 --seconds 40

`ignore_required_affinity` changes the shapes the verbs' programs compile for
(no affinity term is interned), so it is in place before the warm-up, as
`ignore_gangs` is: the window then holds no compile and the answers fail
alone.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

if __name__ == "__main__":
    from benchmarks.tests import chip_control, controls, controls_extender

    controls.CONTROLS.update(controls_extender.CONTROLS)
    if "ignore_required_affinity" in sys.argv:
        controls_extender.ignore_required_affinity(None, None)
    sys.exit(chip_control.main())
