import os
import sys

# NOTE: no XLA_FLAGS device-count override here — tests must see 1 device
# (the 512-device override belongs exclusively to repro/launch/dryrun.py).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
