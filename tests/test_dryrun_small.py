"""Integration: the dry-run machinery (lower + compile + cost/collective
extraction) on a small host mesh, via the shared ``run_prog`` subprocess
fixture (device-count flag must precede jax init)."""
import os


def test_dryrun_small_mesh(run_prog):
    run_prog(os.path.join(os.path.dirname(__file__), "_dryrun_prog.py"))
