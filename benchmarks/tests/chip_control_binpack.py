#!/usr/bin/env python3
"""`chip_control.py` with the accelerator-pool configuration's controls
beside the others:

    python3 benchmarks/tests/chip_control_binpack.py --workload \
        gpu-binpack-5k.backlog --control default_provider_scores --seeds 11 \
        --seconds 40

`default_provider_scores` (`pods_never_bound`: the Policy withheld, the small
pods spread and whole-node pods strand), `ignore_extended_resources`
(`nodes_over_extended_resource`: nine accelerators asked of a node's eight)
and `drop_bindings` (`pods_never_bound`). Both of this configuration's own
controls sit at the measured scheduler, so they go in after the warm-up, as
`run_cell(sabotage=)` places them.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

if __name__ == "__main__":
    from benchmarks.tests import chip_control, controls, controls_binpack

    controls.CONTROLS.update(controls_binpack.CONTROLS)
    sys.exit(chip_control.main())
