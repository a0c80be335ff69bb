"""Reproduce the shape of paper Figs. 4-6: accuracy versus elapsed time.

All eight selection policies train the same CNN on the same synthetic CIFAR
task; the MAB selectors don't change the achievable accuracy, they reach it
*sooner* because their rounds are shorter.  The whole (8 policies x seeds)
grid — bandit selection, resource draws, vmapped local SGD, masked FedAvg,
per-round evaluation — is ONE jit call through fl/engine.accuracy_sweep;
fl/metrics.py turns the traces into ToA@x and common-time-grid curves.

Reduced scale so it finishes in minutes on CPU (paper scale is K=100,
R=500, the 4.6M-param CNN); pass --paper for the real thing on an
accelerator.  Scaling flags mirror examples/eta_sweep.py: --devices
(+ --shard grid|clients) spreads the sweep over a device mesh, and
--chunk-rounds caps peak memory for long runs / large K.

  PYTHONPATH=src python examples/accuracy_sweep.py [--paper] \
      [--devices 8] [--shard grid] [--chunk-rounds 25]
"""

import argparse
import os


def parse_args() -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--paper", action="store_true",
                    help="full paper scale (needs an accelerator)")
    ap.add_argument("--devices", default=None,
                    help="shard over this many devices ('all' = every one)")
    ap.add_argument("--shard", choices=("grid", "clients"), default="grid",
                    help="which axis the devices split")
    ap.add_argument("--chunk-rounds", type=int, default=None,
                    help="pre-sample rounds in chunks of this size")
    return ap.parse_args()


def main() -> None:
    args = parse_args()
    if args.devices not in (None, "all"):
        # CPU-only hosts: force virtual devices BEFORE jax initializes,
        # appending to (not clobbering) any pre-existing XLA_FLAGS; an
        # already-present device-count force wins
        cur = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in cur:
            os.environ["XLA_FLAGS"] = (
                f"{cur} --xla_force_host_platform_device_count="
                f"{int(args.devices)}").strip()
    from repro.utils.compat import use_compile_cache
    use_compile_cache()
    import numpy as np                      # import after XLA_FLAGS is set

    from repro.fl import engine, metrics
    from repro.models import cnn

    if args.paper:
        cfg, kw = cnn.CnnConfig(), dict(
            n_clients=100, n_rounds=500, seeds=3, epochs=5, batch_size=50,
            n_train=50_000, n_test=10_000)
    else:
        cfg = cnn.CnnConfig(image_size=16, channels=(8, 16), pool_after=(0, 1),
                            fc_units=(32,))
        kw = dict(n_clients=30, n_rounds=12, seeds=2, epochs=1,
                  batch_size=20, n_train=3000, n_test=1000, max_samples=60,
                  eval_batch=500, frac_request=0.3)
    devices = args.devices if args.devices in (None, "all") \
        else int(args.devices)
    res = engine.accuracy_sweep("paper-baseline", cfg=cfg, eta=1.5,
                                devices=devices, shard=args.shard,
                                chunk_rounds=args.chunk_rounds, **kw)

    print("ToA@x, seed-averaged (seconds of simulated wall-clock; "
          "lower = reaches the accuracy sooner):\n")
    targets = (0.3, 0.5, 0.7) if not args.paper else (0.5, 0.7, 0.8)
    print(res.summary(targets))

    # accuracy-vs-time curves on a common grid (the Figs. 4-6 x-axis)
    el, acc = res.elapsed, res.accuracy
    grid = np.linspace(0, el.max(), 6)[1:]
    print("\naccuracy at common elapsed-time marks (seed-averaged):\n")
    print(f"{'policy':>16} | " + " | ".join(f"t={t:7.0f}s" for t in grid))
    for i, name in enumerate(res.policies):
        curve = metrics.accuracy_at_time(el[i], acc[i], grid).mean(axis=0)
        print(f"{name:>16} | " + " | ".join(f"{a:9.3f}" for a in curve))
    print("\n(one jit call; rows match paper Figs. 4-6: same final accuracy, "
          "MAB selectors get there in less simulated time)")


if __name__ == "__main__":
    main()
