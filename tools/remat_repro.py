"""Repro/diagnosis for GSPMD's involuntary-remat warnings on the hybrid step.

Builds the exact dryrun hybrid engine (dp2 x mp2 x zero2) on a virtual
8-device CPU mesh, compiles the train step, and greps the optimized HLO
for the offending f32[2,32,64] tensors so we can see which model value
they are. Run:
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python tools/remat_repro.py
"""
import os
import sys

# Force the virtual CPU mesh: this diagnostic never needs the chip.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

import __graft_entry__ as g
import paddle1_tpu as paddle
from paddle1_tpu.core.tensor import Tensor
from paddle1_tpu.distributed import ParallelEngine, build_mesh
from paddle1_tpu.text.models import apply_megatron_sharding


def main():
    model, crit = g._tiny_bert()
    apply_megatron_sharding(model)
    opt = paddle.optimizer.AdamW(learning_rate=1e-4,
                                 parameters=model.parameters())

    def loss_fn(m, batch):
        scores, rel = m(Tensor(batch["ids"]))
        return crit(scores, rel, Tensor(batch["mlm"]), Tensor(batch["nsp"]))

    degrees = {"dp": 2, "mp": 2, "sharding": 2}
    mesh = build_mesh(**degrees, devices=jax.devices()[:8])
    engine = ParallelEngine(model, opt, loss_fn, mesh=mesh, zero_stage=2,
                            clip_global_norm=1.0)
    batch = g._batch(512, 8, 32)
    placed = engine.shard_batch(batch)
    import jax.random as jrandom
    lowered = engine._jit.lower(engine.params, engine.opt_state, placed,
                                jrandom.PRNGKey(0), 1e-4, engine.step_state)
    compiled = lowered.compile()
    hlo = compiled.as_text()
    lines = [ln for ln in hlo.splitlines() if "f32[2,32,64]" in ln]
    print(f"== {len(lines)} HLO lines mention f32[2,32,64] ==")
    for ln in lines:
        print(ln.strip()[:400])


if __name__ == "__main__":
    main()
