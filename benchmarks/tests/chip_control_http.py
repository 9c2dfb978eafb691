#!/usr/bin/env python3
"""`chip_control.py` with the `http` wiring's controls beside the others:

    python3 benchmarks/tests/chip_control_http.py \
        --workload flagship-5k-http.backlog --control forge_acknowledgement \
        --seeds 11 --seconds 40 [--rehearse]

`drop_answer_after_store` and `forge_acknowledgement` (controls_http.py)
forge the wire under one Binding; `ignore_required_affinity` and
`drop_bindings` (controls.py) break the scheduler above it. Every run must
print `correct: false`.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

if __name__ == "__main__":
    from benchmarks.tests import chip_control, controls, controls_http

    controls.CONTROLS.update(controls_http.CONTROLS)
    sys.exit(chip_control.main())
