import os
import sys

# Tests run on the single real CPU device — the 512-device XLA flag is
# set ONLY inside repro.launch.dryrun (per the build instructions).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
# the repo root, for the root-level chip_smoke script's phase functions
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
