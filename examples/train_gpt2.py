"""End-to-end driver (deliverable b): train a ~100M-param GPT-2 for a few
hundred steps — the paper's own llm.c training workload (Table III).

By default this runs the FULL gpt2-124m config for 200 steps, with
checkpointing and fault-tolerant restart enabled; on a CPU that takes a
while, so pass --tiny for a reduced sanity run. The loop runs in this
process (``repro.launch.train.train``), so it holds the chip itself.

    PYTHONPATH=src python examples/train_gpt2.py [--tiny] [--steps N]
"""
import argparse

from repro.launch.train import main


def cli() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    args = ap.parse_args()
    main(["--arch", "gpt2-124m", "--steps", str(args.steps),
          "--batch", str(args.batch), "--seq", str(args.seq),
          "--ckpt-dir", "/tmp/repro_gpt2_ckpt"]
         + ([] if args.tiny else ["--full-size"]))


if __name__ == "__main__":
    cli()
