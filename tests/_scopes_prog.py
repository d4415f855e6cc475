"""Named-scope program, run as a subprocess by tests/test_named_scopes.py
with 4 forced host devices (the XLA flag must be set before jax init).

Compiles the paper-lstm smoke step on a 4-device mesh and checks that
the compiled instructions' ``op_name``s carry the step's scopes, the
``transfer`` stage (the collective) among them:

  * ``data``       — the pure ``("data",)`` mesh (the 4-chip job's layout);
  * ``data_model`` — the ("data", "model") 2x2 mesh (nested shard_map);
  * ``fsdp``       — the dense FSDP step (internlm2-1.8b smoke) on the 2x2
                     mesh: ``fwd_bwd`` only, it has no sync pipeline.
"""
import re
import sys

from harness.cluster import check, force_host_devices

force_host_devices(4)

import jax
import jax.numpy as jnp

from repro.configs import ParallelConfig, TrainConfig, get_config
from repro.core.instrument import STAGES
from repro.launch.mesh import make_data_mesh, make_host_mesh
from repro.models.registry import get_model
from repro.train.trainer import (make_fsdp_dense_step, make_gradient_sync,
                                 make_train_step)

SCOPES = ("fwd_bwd", "sync", *STAGES, "search", "fallback")


def scopes_in(text: str) -> set[str]:
    """Every op_name component but the last (the primitive)."""
    return {part for name in re.findall(r'op_name="([^"]*)"', text)
            for part in name.split("/")[:-1]}


def compiled_text(mesh, arch: str = "paper-lstm",
                  dense_fsdp: bool = False) -> str:
    tc = TrainConfig(optimizer="momentum+clip(threshold_bsearch)",
                     density=0.01, transport="fused_allgather")
    model = get_model(get_config(arch, smoke=True))
    params = jax.eval_shape(model.init_params)
    lr = jax.ShapeDtypeStruct((), jnp.float32)
    batch = model.train_inputs(8, 16)
    if dense_fsdp:
        step = make_fsdp_dense_step(model, mesh, ParallelConfig(), tc,
                                    donate=False)
        return step.lower(params, params, batch, lr).compile().as_text()
    state = jax.eval_shape(make_gradient_sync(tc, mesh).init, params)
    step = make_train_step(model, mesh, None, tc, donate=False)
    return step.lower(params, state, batch, lr).compile().as_text()


def main(case: str) -> None:
    if case == "fsdp":
        found = scopes_in(compiled_text(make_host_mesh(2, 2),
                                        "internlm2-1.8b", dense_fsdp=True))
        check("fsdp step names fwd_bwd", "fwd_bwd" in found)
    else:
        mesh = make_data_mesh(4) if case == "data" else make_host_mesh(2, 2)
        found = scopes_in(compiled_text(mesh))
        for name in SCOPES:
            check(f"{case} step names {name}", name in found)
    print("OK")


if __name__ == "__main__":
    main(sys.argv[1])
