"""CLI: ``python -m comfyui_distributed_tpu serve|info|bench``.

The reference's entry is ComfyUI's ``main.py`` with plugin loading
(``__init__.py:1-29``); standalone, the controller boots directly.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()      # where cdt_boot_seconds{phase=import} begins

import argparse  # noqa: E402
import asyncio  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402


def cmd_serve(args: argparse.Namespace) -> None:
    """Boot a host controller. Every second up to the listening HTTP server
    is put down to a phase (``telemetry/build.py``): ``import``,
    ``backend`` or ``controller`` — spans ``boot.*`` of the one trace
    ``boot`` (``GET /distributed/trace/boot``), ``cdt_boot_seconds``."""
    from .parallel.bootstrap import (ensure_virtual_devices,
                                     init_multihost)
    from .telemetry.build import boot_elapsed, boot_phase
    from .utils.compile_cache import enable_compile_cache

    # CDT_VIRTUAL_DEVICES: stand up the virtual CPU mesh BEFORE anything
    # touches jax (XLA reads the flag once) — the executed mesh tier is
    # then serveable on a chipless host (docs/parallelism.md)
    ensure_virtual_devices()

    import jax  # noqa: F401 — timed as an import, not as the backend's start

    boot_elapsed("import", _T0)
    with boot_phase("backend"):
        # persistent XLA compile cache BEFORE the first trace: full-scale
        # sampler/ladder programs take minutes to compile (the offload
        # ladders recompile per sigma-ladder length) — a server restart or
        # step-count change must not re-pay compiles it has already done
        enable_compile_cache()

        # must precede any jax device query (backend freezes on first
        # touch); no-op without a coordinator (single host)
        init_multihost(
            coordinator_address=getattr(args, "coordinator", None),
            num_processes=getattr(args, "num_hosts", None),
            process_id=getattr(args, "host_index", None),
        )

    with boot_phase("import"):
        from .api.app import run_app
        from .cluster.controller import Controller
        from .parallel.mesh import device_census
        from .utils.config import update_config
        from .utils.logging import log
        from .workers.detection import auto_populate_hosts
        from .workers.process_manager import (delayed_auto_launch,
                                              get_worker_manager)

    # claim the devices NOW: this process owns every chip of the host for
    # its lifetime (docs/deployment.md, "One process per chip"), and a
    # backend that cannot start is a failed boot — not a server that finds
    # out on its first request, and never a server on another platform
    with boot_phase("backend"):
        census = device_census()
    log(f"devices: {len(census)} x {census[0]['platform']} "
        f"({census[0]['kind']})")

    with boot_phase("controller"):
        controller = Controller()
        if not controller.is_worker and not controller.load_config().get(
                "settings", {}).get("has_auto_populated_workers"):
            # first-launch auto-configuration (reference auto-populates
            # one worker per CUDA device, web/masterDetection.js:36-100;
            # here: one controller per TPU slice host advertised by the
            # runtime)
            update_config(auto_populate_hosts, controller.config_path)

    async def main() -> None:
        with boot_phase("controller"):
            runner = await run_app(controller, host=args.host,
                                   port=args.port)
        if not controller.is_worker:
            manager = get_worker_manager()
            asyncio.ensure_future(delayed_auto_launch(manager))

            import atexit

            atexit.register(manager.cleanup_all)
        stop = asyncio.Event()

        import signal

        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            try:
                loop.add_signal_handler(sig, stop.set)
            except NotImplementedError:  # pragma: no cover - windows
                pass
        await stop.wait()
        log("shutting down")
        await runner.cleanup()

    asyncio.run(main())


def cmd_info(args: argparse.Namespace) -> None:
    from .cluster.controller import Controller

    controller = Controller()
    print(json.dumps(controller.system_info(), indent=2, default=str))


def cmd_bench(args: argparse.Namespace) -> None:
    import runpy
    from pathlib import Path

    bench = Path(__file__).resolve().parent.parent / "bench.py"
    runpy.run_path(str(bench), run_name="__main__")


def cmd_convert(args: argparse.Namespace) -> None:
    """Published single-file .safetensors → orbax checkpoint dir usable via
    CDT_CHECKPOINT_ROOT (the reference ships model *names* and assumes
    ComfyUI loads them; here conversion is an explicit, verified step)."""
    from pathlib import Path

    from .models.registry import PRESETS, ModelBundle

    preset = PRESETS.get(args.preset)
    if preset is None:
        sys.exit(f"unknown preset {args.preset!r}; have {sorted(PRESETS)}")
    # abstract core: the converter only needs leaf shapes, and every core
    # leaf is about to be overwritten — skip the (FLUX-size: ~48 GB)
    # random init
    if preset.moe_boundary is not None and not getattr(
            args, "checkpoint_low", None):
        # fail BEFORE converting 28 GB: a dual-expert checkpoint without
        # its low expert would only crash at save time (abstract leaves)
        sys.exit(f"preset {args.preset!r} is a dual-expert model — pass "
                 "the low-noise transformer via --checkpoint-low")
    bundle = ModelBundle(preset, abstract_core=True)
    if getattr(args, "checkpoint_low", None):
        # WAN-2.2 dual-expert releases: --checkpoint is the high-noise
        # transformer, --checkpoint-low the low-noise one
        bundle.load_safetensors_moe(Path(args.checkpoint),
                                    Path(args.checkpoint_low))
    else:
        bundle.load_safetensors_checkpoint(Path(args.checkpoint))
    if getattr(args, "t5", None) or getattr(args, "clip_l", None):
        bundle.load_text_encoder_files(
            t5=Path(args.t5) if args.t5 else None,
            clip_l=Path(args.clip_l) if args.clip_l else None)
    if getattr(args, "vae", None):
        bundle.load_vae_file(Path(args.vae))
    bundle.save_checkpoint(Path(args.out))
    print(json.dumps({"preset": args.preset, "out": str(args.out),
                      "entries": sorted(bundle._state_entries())}))


def main(argv: list[str] | None = None) -> None:
    from .parallel.bootstrap import ensure_virtual_devices

    # CDT_VIRTUAL_DEVICES must land before the FIRST jax touch
    ensure_virtual_devices()

    p = argparse.ArgumentParser(prog="comfyui_distributed_tpu")
    sub = p.add_subparsers(dest="command", required=True)

    serve = sub.add_parser("serve", help="run a host controller")
    serve.add_argument("--host", default="0.0.0.0")
    serve.add_argument("--port", type=int, default=None)
    serve.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                       help="multi-host: JAX coordinator address "
                            "(env CDT_COORDINATOR)")
    serve.add_argument("--num-hosts", type=int, default=None,
                       help="multi-host: total host processes "
                            "(env CDT_NUM_HOSTS)")
    serve.add_argument("--host-index", type=int, default=None,
                       help="multi-host: this host's process id "
                            "(env CDT_HOST_INDEX)")
    serve.set_defaults(fn=cmd_serve)

    info = sub.add_parser("info", help="print system/device info")
    info.set_defaults(fn=cmd_info)

    bench = sub.add_parser("bench", help="run the throughput benchmark")
    bench.set_defaults(fn=cmd_bench)

    conv = sub.add_parser(
        "convert", help="convert a single-file .safetensors checkpoint")
    conv.add_argument("--checkpoint", required=True)
    conv.add_argument("--checkpoint-low", dest="checkpoint_low", default=None,
                      help="wan-2.2 dual-expert: low-noise transformer "
                           ".safetensors (--checkpoint is then the "
                           "high-noise expert)")
    conv.add_argument("--preset", default="sdxl")
    conv.add_argument("--out", required=True)
    conv.add_argument("--t5", default=None,
                      help="flux: standalone t5xxl .safetensors (HF layout)")
    conv.add_argument("--clip-l", dest="clip_l", default=None,
                      help="flux: standalone clip_l .safetensors (HF layout)")
    conv.add_argument("--vae", default=None,
                      help="standalone VAE .safetensors (BFL ae / SD VAE / "
                           "LDM-embedded layouts auto-detected)")
    conv.set_defaults(fn=cmd_convert)

    args = p.parse_args(argv)
    args.fn(args)


if __name__ == "__main__":
    main()
