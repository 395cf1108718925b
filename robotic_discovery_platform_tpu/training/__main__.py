"""Training CLI -- the reference's ``python scripts/train_segmenter.py``
entry point as a module main (reference: scripts/train_segmenter.py:213-214
calls train_model() with module-constant hyperparameters; here the same
constants are the config defaults and everything is overridable).

Usage:
    python -m robotic_discovery_platform_tpu.training \
        --train.dataset_dir ml/datasets/processed \
        --train.epochs 50 --model.compute_dtype bfloat16 [--resume]

With ``--mesh.data/--mesh.spatial/--mesh.model`` sizes >1 the run shards
over the device mesh (parallel/); ``--resume`` restores the latest orbax
checkpoint under ``train.checkpoint_dir``. Returns the process exit code:
0 on success, 2 on a config/dataset error.
"""

from __future__ import annotations

import argparse
import json
import sys


def main(argv=None) -> int:
    from robotic_discovery_platform_tpu.utils.platforms import (
        enable_compile_cache,
    )

    enable_compile_cache()

    from robotic_discovery_platform_tpu.utils import config as config_lib

    parser = argparse.ArgumentParser(
        prog="python -m robotic_discovery_platform_tpu.training",
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    parser.add_argument("--config", type=str, default=None,
                        help="JSON config file (PlatformConfig shape)")
    parser.add_argument("--resume", action="store_true",
                        help="resume from the latest checkpoint")
    parser.add_argument("--no-register", action="store_true",
                        help="skip model-registry registration")
    config_lib.add_flags(parser, config_lib.PlatformConfig)
    args = parser.parse_args(argv)
    cfg = config_lib.PlatformConfig()
    if args.config:
        from pathlib import Path

        cfg = config_lib.from_dict(
            config_lib.PlatformConfig, json.loads(Path(args.config).read_text())
        )
    cfg = config_lib.apply_flags(cfg, args)

    # Mesh semantics: untouched defaults = the reference's single-device
    # path; ANY explicit --mesh.* override builds the mesh, including the
    # documented infer-from-devices sizes (<= 0, utils/config.MeshConfig).
    mesh = None
    if cfg.mesh != config_lib.MeshConfig():
        from robotic_discovery_platform_tpu import parallel

        mesh = parallel.make_mesh(cfg.mesh)

    from robotic_discovery_platform_tpu.training.trainer import train_model

    try:
        res = train_model(
            cfg.train, cfg.model, resume=args.resume, mesh=mesh,
            register=not args.no_register,
        )
    except (FileNotFoundError, ValueError) as e:
        # config/dataset problems get the one-line CLI error the docstring
        # promises, not a traceback; unexpected errors still raise loudly
        print(f"error: {e}", file=sys.stderr)
        return 2
    print(json.dumps(res.to_jsonable()))
    return 0


if __name__ == "__main__":
    sys.exit(main())
