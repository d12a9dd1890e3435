"""End-to-end training driver: a ~small LM for a few hundred steps on CPU.

Trains the reduced granite-3-2b family config on the synthetic Markov-LM
data pipeline with the pure-JAX AdamW, checkpoints, restores, and verifies
the loss went down.  Pass ``--arch`` for any of the 10 zoo families and
``--steps`` to train longer.

    PYTHONPATH=src python examples/train_small.py --steps 300
"""
import argparse

from repro.configs.base import reduced
from repro.configs.registry import ARCHS
from repro.train.loop import train
from repro.train import checkpoint as ckpt


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b", choices=sorted(ARCHS))
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt", default="/tmp/repro_ckpt/train_small")
    args = ap.parse_args()

    cfg = reduced(ARCHS[args.arch], n_layers=2, d_model=128, vocab=512)
    print(f"training reduced {args.arch} ({cfg.family}) for "
          f"{args.steps} steps")
    state, losses = train(cfg, steps=args.steps, batch=args.batch,
                          seq_len=args.seq, checkpoint_path=args.ckpt)

    first, last = sum(losses[:10]) / 10, sum(losses[-10:]) / 10
    print(f"\nloss {first:.3f} → {last:.3f} "
          f"({'LEARNED' if last < first - 0.1 else 'no improvement?'})")

    restored = ckpt.load(args.ckpt, state.params)
    print("checkpoint restored:",
          all((a == b).all() for a, b in zip(
              __import__('jax').tree.leaves(restored),
              __import__('jax').tree.leaves(state.params))))


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
