"""Training driver: train a small LM on the synthetic corpus with the full
substrate (data pipeline -> model -> AdamW -> checkpoint), then publish the
checkpoint to the WeightStore so the serving side can freshen against it
(version-staleness refetch).

The paper is a serving paper, so the REQUIRED end-to-end driver is
serve_chain.py; this demonstrates the training substrate.  Defaults are
laptop-sized; ``--dim 768 --layers 12 --steps 300`` gives a ~100M model.

Run:  PYTHONPATH=src python examples/train_small.py --steps 60
"""
import argparse
import dataclasses
import os
import tempfile

import jax

from repro.configs import get_config
from repro.data import DataConfig, packed_batches
from repro.launch.compile_cache import configure_compile_cache
from repro.models import make_model
from repro.serving import WeightStore
from repro.train import OptimizerConfig, Trainer, TrainerConfig

if __name__ == "__main__":
    configure_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--dim", type=int, default=192)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--vocab", type=int, default=512)
    ap.add_argument("--arch", default="qwen2-0.5b")
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced(n_layers=args.layers,
                                        d_model=args.dim, vocab=args.vocab)
    cfg = dataclasses.replace(cfg, dtype="float32")
    model = make_model(cfg)
    n_params = cfg.param_count()
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M")

    data = packed_batches(DataConfig(vocab_size=args.vocab, seq_len=args.seq,
                                     batch_size=args.batch, seed=0))
    ckpt_dir = tempfile.mkdtemp(prefix="train-small-")
    trainer = Trainer(
        model,
        OptimizerConfig(peak_lr=1e-3, warmup_steps=20, total_steps=args.steps),
        TrainerConfig(steps=args.steps, checkpoint_every=max(10, args.steps // 3),
                      checkpoint_path=os.path.join(ckpt_dir, "ck.ckpt"),
                      num_microbatches=2),
        data)
    hist = trainer.run()
    for h in hist[:: max(1, args.steps // 10)]:
        print(f"  step {h['step']:4d}  loss {h['loss']:.4f}  "
              f"gnorm {h['grad_norm']:.2f}  lr {h['lr']:.2e}  "
              f"{h['seconds']*1e3:.0f}ms")
    print(f"final loss: {hist[-1]['loss']:.4f} "
          f"(start {hist[0]['loss']:.4f})")

    # publish for the serving side: freshen's version_fn sees v2 and refetches
    store = WeightStore(os.path.join(ckpt_dir, "store"))
    v = store.publish("trained-small", trainer.params)
    print(f"published to WeightStore as version {v} "
          f"({store.nbytes('trained-small')/1e6:.1f} MB)")
