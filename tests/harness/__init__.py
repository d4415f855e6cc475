"""Simulated-cluster harness: an N-way worker mesh on forced host devices.

Entry points (see ``cluster.py``):

* ``force_host_devices(n)`` — set the XLA flag that splits the host CPU
  into ``n`` devices (must run before jax initializes).
* ``make_node_mesh(nodes)`` — 2-axis ``("node", "local")`` mesh (the
  simulated multi-node cluster the ``hierarchical`` transport syncs over).
* ``train_and_eval(...)`` — a real short training run through
  ``repro.train.trainer.Trainer`` + held-out loss, on the launcher's
  1-D ``("data",)`` mesh (``repro.launch.mesh.make_data_mesh``; the
  trainer's fully-manual pure-data-parallel path) or the node mesh.
* ``run_cluster(spec)`` — the subprocess driver (device forcing must
  happen before jax init, so multi-device runs go through
  ``_cluster_prog.py`` in a child process).
* ``convergence_pair(...)`` — sparse-with-corrections vs dense baseline
  on the same mesh/budget; what the tier-2 tests and
  ``benchmarks/tab1_convergence.py`` consume.
"""
from .cluster import (CLUSTER_PROG, check, convergence_pair,
                      force_host_devices, make_node_mesh, run_cluster,
                      subprocess_env, train_and_eval)

__all__ = ["CLUSTER_PROG", "check", "convergence_pair",
           "force_host_devices", "make_node_mesh", "run_cluster",
           "subprocess_env", "train_and_eval"]
