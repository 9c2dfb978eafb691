#!/usr/bin/env python3
"""`chip_control.py` with the preemption configuration's controls beside the
others:

    python3 benchmarks/tests/chip_control_preempt.py --workload \
        preempt-5k.backlog --control skip_reprieve --seeds 11 --seconds 40

`skip_reprieve` (`victims_beyond_minimum`, one a node), `evict_unhanded_nodes`
(`victims_evicted_for_nothing`) and `drop_bindings` (`pods_never_bound`), each
`correct: false`. Both of this configuration's own controls sit at the
measured scheduler's evictor, so they go in after the warm-up, as
`run_cell(sabotage=)` places them.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

if __name__ == "__main__":
    from benchmarks.tests import chip_control, controls, controls_preempt

    controls.CONTROLS.update(controls_preempt.CONTROLS)
    sys.exit(chip_control.main())
