"""CPU tests of the yardstick itself: ``python -m pytest benchmark/selftest``.
They are run by hand and in rehearsal, not by the repo's tier-1 command."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
