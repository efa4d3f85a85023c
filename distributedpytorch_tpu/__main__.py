"""CLI driver: ``python -m distributedpytorch_tpu [--config c.json] [k=v ...]``.

The runnable equivalent of ``python train_pascal.py`` (the reference's only
entry point — a module-level script with inline constants,
train_pascal.py:41-309), but configured by JSON + dotted-path overrides:

    python -m distributedpytorch_tpu data.root=/data/voc optim.lr=1e-7
    python -m distributedpytorch_tpu --config exp.json epochs=50
    python -m distributedpytorch_tpu --fake-data epochs=2   # smoke run

Multi-host: launch the same command on every host of the pod;
``jax.distributed.initialize`` handles rendezvous, the loaders shard by
process index, and only process 0 writes logs/checkpoint metadata.
"""

from __future__ import annotations

import argparse
import sys

from .backend_health import enable_compile_cache
from .train import Config, Trainer, apply_overrides, from_json


def main(argv: list[str] | None = None) -> int:
    # Serve mode delegates wholesale: the inference service has its own
    # argument surface (serve/__main__.py), and mixing it into the training
    # parser would tangle two unrelated CLIs.  `--serve` must lead.
    argv = sys.argv[1:] if argv is None else list(argv)
    if argv[:1] == ["--serve"]:
        from .serve.__main__ import main as serve_main
        return serve_main(argv[1:])
    # every later run of the same configuration finds its step programs
    # compiled (the flagship step is a minute of XLA on a v5e)
    enable_compile_cache()
    parser = argparse.ArgumentParser(
        prog="distributedpytorch_tpu",
        description="TPU-native interactive-segmentation training",
        epilog="Serving: `python -m distributedpytorch_tpu --serve ...` "
               "(equivalently `python -m distributedpytorch_tpu.serve`) "
               "starts the batched inference service; see its --help.")
    parser.add_argument("--config", help="JSON config file")
    parser.add_argument("--fake-data", action="store_true",
                        help="synthetic VOC fixture (smoke runs, no dataset)")
    parser.add_argument("--validate-only", action="store_true",
                        help="run the eval protocol once and exit")
    parser.add_argument("--predict", metavar="IMAGE",
                        help="inference mode: segment IMAGE from --points "
                             "clicks using the run in --run-dir")
    parser.add_argument("--run-dir",
                        help="training run dir (config.json + checkpoints/) "
                             "for --predict")
    parser.add_argument("--points",
                        help='4 extreme-point clicks "x1,y1 x2,y2 x3,y3 '
                             'x4,y4" for --predict on instance-task runs '
                             "(semantic runs segment the whole image)")
    parser.add_argument("--out", default="mask.png",
                        help="output mask PNG for --predict")
    parser.add_argument("--overlay",
                        help="also write an RGB overlay PNG (--predict)")
    parser.add_argument("--slide", action="store_true",
                        help="semantic runs: sliding-window full-resolution "
                             "inference instead of whole-image resize")
    parser.add_argument("--threshold", type=float, default=None,
                        help="binarization threshold for --predict on "
                             "instance-task runs (default 0.5)")
    parser.add_argument("--distributed", action="store_true",
                        help="call jax.distributed.initialize() first "
                             "(multi-host pods)")
    parser.add_argument("overrides", nargs="*",
                        help="dotted config overrides, e.g. optim.lr=1e-7")
    args = parser.parse_args(argv)

    # Predict mode first: it must not fall into the multi-host rendezvous
    # below (jax.distributed.initialize() blocks waiting for peers).
    if args.predict:
        if not args.run_dir:
            parser.error("--predict requires --run-dir (--points too for "
                         "instance-task runs)")
        if args.config or args.fake_data or args.validate_only \
                or args.distributed or args.overrides:
            parser.error(
                "--predict reads its configuration from <run-dir>/"
                "config.json; --config/--fake-data/--validate-only/"
                "--distributed/overrides do not apply (got "
                f"{args.overrides or 'training-mode flags'})")
        from .predict import predict_cli
        try:
            summary = predict_cli(args.run_dir, args.predict, args.points,
                                  args.out, threshold=args.threshold,
                                  overlay_path=args.overlay,
                                  slide=args.slide)
        except ValueError as e:  # missing points / bad clicks / wrong task
            parser.error(str(e))
        print(summary)
        return 0

    if args.distributed:
        import jax
        jax.distributed.initialize()

    cfg = from_json(args.config) if args.config else Config()
    if args.fake_data:
        cfg = apply_overrides(cfg, {"data.fake": True})
    if args.overrides:
        cfg = apply_overrides(cfg, args.overrides)

    trainer = Trainer(cfg)
    try:
        if args.validate_only:
            metrics = trainer.validate()
            print({k: v for k, v in metrics.items() if k != "_first_batch"})
        else:
            trainer.fit()
    finally:
        trainer.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
