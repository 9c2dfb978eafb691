#!/usr/bin/env python3
"""`chip_control.py` with the volume configuration's controls beside the
others:

    python3 benchmarks/tests/chip_control_volumes.py --workload \
        csi-pvs-5k.backlog --control ignore_volumes --seeds 11 --seconds 40

`ignore_volumes` (`node_volume_state_wrong`; at the rehearsal size
`nodes_over_volume_limit` too), `ignore_volume_limits` (at the rehearsal
size `nodes_over_volume_limit`; sound at the published size, where the limit
refuses nothing) and `drop_bindings` (`pods_never_bound`). Both of this
configuration's own controls sit at the measured scheduler's decode, so they
go in after the warm-up, as `run_cell(sabotage=)` places them.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

if __name__ == "__main__":
    from benchmarks.tests import chip_control, controls, controls_volumes

    controls.CONTROLS.update(controls_volumes.CONTROLS)
    sys.exit(chip_control.main())
