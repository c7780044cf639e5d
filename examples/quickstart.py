"""Quickstart: the paper's algorithm end-to-end in 60 seconds on CPU.

1. Reproduce Fig. 9 (heavy workload) through `repro.api.Session`: dynamic
   partitioning vs sequential, then compare partition policies.
2. Run the fused multi-tenant Pallas GEMM (compiled on a TPU, in the
   Pallas interpreter on the CPU) and check it against the oracle.
3. Train a reduced llama3.2-3b for 30 steps and watch the loss drop.

    PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp

# -- 1. the paper's simulation, via the API front door -------------------
from repro.api import Session, list_policies
from repro.sim.runner import format_report

print("=" * 70)
print("1) Fig. 9 reproduction — heavy workload (policy='equal' = Alg. 1)")
print("=" * 70)
res = Session(policy="equal", backend="sim").run("heavy")
print(format_report(res))

print()
print("policy comparison (heavy):")
for pol in list_policies():
    r = Session(policy=pol, backend="sim").run("heavy")
    print(f"  {pol:<14} time saving {r.time_saving*100:5.1f}%  "
          f"energy saving {r.energy_saving*100:5.1f}%")

# -- 2. the kernel -------------------------------------------------------
from repro.kernels import fused_tenant_gemm

print()
print("=" * 70)
interpret = jax.devices()[0].platform == "cpu"
print("2) fused multi-tenant partitioned-WS GEMM (Pallas"
      + (", interpret)" if interpret else ")"))
print("=" * 70)
key = jax.random.key(0)
xs, ws = [], []
for i, (t, k, n) in enumerate([(100, 200, 96), (256, 128, 300)]):
    k1, k2 = jax.random.split(jax.random.fold_in(key, i))
    xs.append(jax.random.normal(k1, (t, k), jnp.float32))
    ws.append(jax.random.normal(k2, (k, n), jnp.float32))
outs = fused_tenant_gemm(xs, ws, interpret=interpret)
for i, (x, w, o) in enumerate(zip(xs, ws, outs)):
    ref = jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)
    err = float(jnp.abs(o - ref).max())
    print(f"tenant {i}: {x.shape} @ {w.shape} -> {o.shape}, "
          f"max err {err:.2e}")
    assert err < 1e-3

# -- 3. train ------------------------------------------------------------
from repro.configs import get
from repro.launch.mesh import make_host_mesh
from repro.training.data import DataConfig, make_batch
from repro.training.optimizer import OptConfig
from repro.training.train_loop import TrainConfig, init_sharded, \
    make_train_step

print()
print("=" * 70)
print("3) train reduced llama3.2-3b, 30 steps")
print("=" * 70)
cfg = get("llama3.2-3b").smoke
mesh = make_host_mesh()
params, opt_state = init_sharded(cfg, mesh, seed=0)
_, jitted = make_train_step(
    cfg, mesh, TrainConfig(opt=OptConfig(lr=1e-2, warmup_steps=5,
                                         total_steps=100)))
dcfg = DataConfig(vocab=cfg.vocab, batch=8, seq=32, seed=0)
step_fn = None
first = last = None
for i in range(30):
    batch = make_batch(dcfg, i, mesh)
    if step_fn is None:
        step_fn = jitted(params, opt_state, batch)
    params, opt_state, m = step_fn(params, opt_state, batch)
    if i == 0:
        first = float(m["loss"])
    last = float(m["loss"])
    if (i + 1) % 10 == 0:
        print(f"step {i+1:3d}  loss {last:.4f}")
assert last < first, "loss did not drop"
print(f"\nloss {first:.3f} -> {last:.3f}: OK")
print("\nquickstart complete.")
