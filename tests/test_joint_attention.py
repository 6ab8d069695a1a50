"""The two-segment attention call's tests, in the smoke tier.

They are written where the kernels' and the DiT's other tests are —
``test_flash_attention.py`` and ``test_dit_flow.py`` — and both files are
marked ``slow`` as a whole. The benchmark's two SD3 goldens cannot see the
DiT (its output projection is zero-initialised: PERF.md §7), so these are
the tests that hold the joint kernel and every run of the smoke tier must
make them: collected from here they carry no ``slow`` mark."""

from test_dit_flow import (  # noqa: F401
    test_joint_blocks_on_the_packed_tier_match_the_xla_arm,
)
from test_flash_attention import TestJointSegments  # noqa: F401
