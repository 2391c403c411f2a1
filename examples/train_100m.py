"""End-to-end training driver: a ~100M-parameter dense model for a few
hundred steps on the synthetic corpus (deliverable b).

    PYTHONPATH=src python examples/train_100m.py [--steps 300]

The config is the granite-8b family scaled to ~100M (family-faithful:
GQA + SwiGLU + RMSNorm); loss falls from ~9 to <5 over the run.
"""

import argparse
import dataclasses
import json

from repro.compile_cache import use_compile_cache
from repro.configs import get_config
from repro.launch.train import train
import repro.launch.train as T
from repro.models.config import ModelConfig
from repro.models.transformer import Model


def hundred_m() -> ModelConfig:
    base = get_config("granite-8b")
    return dataclasses.replace(
        base,
        name="granite-100m",
        n_layers=10,
        d_model=640,
        n_heads=10,
        n_kv_heads=5,
        head_dim=64,
        d_ff=2560,
        vocab_size=32768,
        dtype="float32",
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    args = ap.parse_args()
    use_compile_cache()

    cfg = hundred_m()
    n = cfg.param_count()
    print(f"config: {cfg.name}  params={n / 1e6:.1f}M")

    hist = train(cfg.name, steps=args.steps, batch=args.batch,
                 seq=args.seq, log_every=20, config=cfg,
                 checkpoint_dir="results/ckpt_100m")
    print(json.dumps({"first": hist[0], "last": hist[-1]}, indent=1))


if __name__ == "__main__":
    main()
