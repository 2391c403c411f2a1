"""Quickstart: the three layers of the framework in one script.

1. the paper's scheduler core (RAS) placing a deadline-constrained workload,
2. a model from the assigned-architecture registry running a forward pass,
3. a micro training run through the shared substrate.

    PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp

from repro.compile_cache import use_compile_cache
from repro.configs import get_config, reduced
from repro.core.scheduler import RASScheduler
from repro.core.tasks import LPRequest, Priority, Task
from repro.launch.train import train
from repro.models.transformer import Model

use_compile_cache()

# -- 1. deadline-constrained scheduling ------------------------------------
print("== 1. RAS scheduler ==")
sched = RASScheduler(n_devices=4, bandwidth_bps=20e6, seed=0)
hp = Task(Priority.HIGH, source_device=0, release_time=0.0, deadline=3.0,
          frame_id=0)
res = sched.schedule_hp(hp, now=0.0)
print(f"HP task -> device {hp.device} at t={hp.start_time:.2f}s "
      f"(latency {res.latency * 1e3:.2f} ms)")

lp = LPRequest(
    [Task(Priority.LOW, 0, 1.0, 40.0, frame_id=0) for _ in range(4)],
    source_device=0, release_time=1.0,
)
res = sched.schedule_lp(lp, now=1.0)
for t in lp.tasks:
    where = "local" if not t.offloaded else f"offloaded->dev{t.device}"
    print(f"  LP task {t.task_id}: {where}, [{t.start_time:.2f}, "
          f"{t.end_time:.2f}]s, cfg={t.config.name}")
print(f"LP request latency: {res.latency * 1e3:.2f} ms")

# -- 2. a model from the assigned pool --------------------------------------
print("\n== 2. assigned architecture (reduced gemma2-2b) ==")
cfg = reduced(get_config("gemma2-2b"))
model = Model(cfg)
params = model.init(jax.random.PRNGKey(0))
batch = {"tokens": jnp.zeros((2, 32), jnp.int32)}
logits, _ = jax.jit(model.forward)(params, batch)
print(f"forward: tokens (2, 32) -> logits {logits.shape}")

# -- 3. a micro training run --------------------------------------------------
print("\n== 3. train 20 steps ==")
hist = train("qwen2.5-3b", steps=20, batch=4, seq=64, log_every=10)
print(f"loss: {hist[0]['loss']:.3f} -> {hist[-1]['loss']:.3f}")
