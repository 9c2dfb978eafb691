#!/usr/bin/env python3
"""`chip_control.py` with the DaemonSet configuration's controls beside the
others:

    python3 benchmarks/tests/chip_control_daemons.py --workload \
        daemonset-5k.backlog --control ignore_pins --seeds 11 --seconds 40

`ignore_pins` (`pinned_elsewhere`: the pods land by score, not by name),
`drop_daemon_tolerations` (`pods_never_bound` and `daemon_missing`, the 400
pods of the cordoned nodes) and `drop_bindings` (`pods_never_bound`). Both of this configuration's own controls sit at the
measured scheduler's decode, so they go in after the warm-up, as
`run_cell(sabotage=)` places them.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

if __name__ == "__main__":
    from benchmarks.tests import chip_control, controls, controls_daemons

    controls.CONTROLS.update(controls_daemons.CONTROLS)
    sys.exit(chip_control.main())
