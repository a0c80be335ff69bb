"""End-to-end FL training driver (deliverable b): trains the paper's CNN
with MAB client selection, checkpoints, then simulates a crash and resumes.

  PYTHONPATH=src python examples/fl_train.py
"""

import tempfile

from repro.utils.compat import use_compile_cache
from repro.launch.train import main as train_main


def main() -> None:
    use_compile_cache()
    with tempfile.TemporaryDirectory() as ckpt:
        print("=== phase 1: train 6 rounds with checkpointing ===")
        train_main(["--arch", "cifar-cnn", "--policy", "elementwise_ucb",
                    "--rounds", "6", "--clients", "12", "--fast",
                    "--ckpt-dir", ckpt, "--ckpt-every", "3"])
        print("\n=== phase 2: 'crash' and resume from the checkpoint ===")
        train_main(["--arch", "cifar-cnn", "--policy", "elementwise_ucb",
                    "--rounds", "8", "--clients", "12", "--fast",
                    "--ckpt-dir", ckpt, "--ckpt-every", "3", "--resume"])


if __name__ == "__main__":
    main()
