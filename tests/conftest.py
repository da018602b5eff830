import os
import sys
import tempfile

# NOTE: do NOT set XLA_FLAGS / host device count here — smoke tests and
# benchmarks must see the single real CPU device (tests that need a mesh
# of virtual devices start a process of their own).
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

# Isolate the persistent rendered-SQL plan cache: without this, every
# SQLEngine default would read/write the developer's real
# ~/.cache/repro/plan_cache.db — cross-run state that could mask (or
# cause) differential failures.  A per-session temp store keeps the
# persistence code path exercised while staying hermetic.
if "REPRO_PLAN_CACHE" not in os.environ:
    os.environ["REPRO_PLAN_CACHE"] = os.path.join(
        tempfile.mkdtemp(prefix="repro_plan_cache_"), "plans.db")
