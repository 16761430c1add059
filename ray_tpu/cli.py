"""``python -m ray_tpu`` — cluster status/state/metrics CLI.

Capability parity with the reference's ``ray status`` / ``ray list``
CLI (reference: ``python/ray/scripts/scripts.py``,
``util/state/state_cli.py``), attaching to a running head via the
``session.json`` discovery file each head writes at startup.

Commands:
    python -m ray_tpu start --head            # standalone head daemon
    python -m ray_tpu start --address H:P     # node daemon joining a head
    python -m ray_tpu status                  # cluster summary
    python -m ray_tpu list nodes|workers|actors|placement_groups|tasks
    python -m ray_tpu metrics                 # prometheus text
    python -m ray_tpu timeline out.json       # chrome-trace export
    python -m ray_tpu dashboard               # print dashboard URL

``start --head`` keeps the control plane alive independently of any
driver (reference: ``ray start --head``); drivers then attach with
``rt.init(address="auto")`` locally, ``rt.init(address=<sock>)`` on the
same host, or ``rt.init(address="host:port")`` from another machine.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time as _time


def _find_session(session_dir: str = "") -> dict:
    if session_dir:
        candidates = [os.path.join(session_dir, "session.json")]
    else:
        root = os.path.join(os.environ.get("TMPDIR", "/tmp"), "ray_tpu")
        candidates = sorted(
            glob.glob(os.path.join(root, "*", "session.json")),
            key=os.path.getmtime, reverse=True)
    for path in candidates:
        try:
            with open(path) as f:
                info = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        # Stale session? The head's pid must still be alive.
        try:
            os.kill(info["pid"], 0)
        except (OSError, KeyError):
            continue
        info["session_dir"] = os.path.dirname(path)
        return info
    raise SystemExit(
        "no live ray_tpu session found (is a driver running?); "
        "pass --session-dir explicitly")


def _connect(info: dict):
    import ray_tpu as rt

    rt.init(address=info["head_sock"])
    return rt


def _cmd_serve(args) -> int:
    """``serve deploy/run/status/config/shutdown`` against the running
    cluster (reference: ``serve/scripts.py``)."""
    from ray_tpu import serve
    from ray_tpu.serve import schema

    if args.serve_cmd == "deploy":
        import yaml

        with open(args.config_file) as f:
            cfg = yaml.safe_load(f)
        names = schema.deploy_config(cfg)
        print(f"deployed applications: {', '.join(names)}")
    elif args.serve_cmd == "run":
        app = schema.import_application(args.import_path)
        print(f"running app {args.name!r} at route "
              f"{args.route_prefix!r}; ctrl-c to exit")
        serve.run(app, name=args.name, route_prefix=args.route_prefix,
                  blocking=True)
    elif args.serve_cmd == "status":
        print(json.dumps(serve.status(), indent=1, default=str))
    elif args.serve_cmd == "config":
        import yaml

        cfg = schema.get_last_config()
        print(yaml.safe_dump(cfg) if cfg else "# no config deployed")
    elif args.serve_cmd == "shutdown":
        serve.shutdown()
        print("serve shut down")
    return 0


def _cmd_start(args) -> int:
    if args.address:   # join an existing head as a node daemon
        import tempfile

        from ._private import node_main
        from ._private.accelerators import local_chip_count

        session_dir = args.session_dir or tempfile.mkdtemp(
            prefix="ray_tpu_node_")
        # Same TPU autodetection as the head path: joining a TPU host
        # without --num-tpus must still advertise its chips.
        num_tpus = (args.num_tpus if args.num_tpus is not None
                    else float(local_chip_count()))
        argv = ["--head", args.address, "--session-dir", session_dir,
                "--num-cpus", str(args.num_cpus)]
        if num_tpus:
            argv += ["--num-tpus", str(num_tpus)]
        if getattr(args, "die_with_parent", False):
            argv += ["--die-with-parent"]
        return node_main.main(argv)
    if not args.head:
        raise SystemExit("start requires --head or --address")
    # Standalone head (reference `ray start --head`): the control plane
    # outlives any driver; session.json is the discovery file.
    import asyncio
    import time

    from ._private.accelerators import gang_resources, local_chip_count
    from ._private.config import Config, set_global_config
    from ._private.head import HeadService

    session_dir = args.session_dir or os.path.join(
        os.environ.get("TMPDIR", "/tmp"), "ray_tpu",
        f"session_{int(time.time() * 1000)}_{os.getpid()}")
    os.makedirs(session_dir, exist_ok=True)
    config = Config({})
    set_global_config(config)
    total = {"CPU": float(args.num_cpus),
             "TPU": float(args.num_tpus if args.num_tpus is not None
                          else local_chip_count()),
             # Same default total as rt.init()'s embedded head — a
             # missing "memory" resource would strand memory-requesting
             # leases forever.
             "memory": float(os.sysconf("SC_PAGE_SIZE")
                             * os.sysconf("SC_PHYS_PAGES"))}
    for k, v in gang_resources(total["TPU"]).items():
        total.setdefault(k, v)

    from ._private import reaper

    reaper.become_subreaper()
    if getattr(args, "die_with_parent", False):
        reaper.die_with_parent()
        reaper.start_orphan_watchdog()

    async def run():
        import signal

        head = HeadService(session_dir, config, total)
        await head.start()
        print(f"head started\n  session: {session_dir}\n"
              f"  sock:    {head.sock_path}\n"
              f"  tcp:     {head.tcp_address[0]}:{head.tcp_address[1]}",
              flush=True)
        # SIGTERM (systemd/docker stop) must run head.stop() like the
        # node daemon does, not die mid-loop with a stale session.json.
        loop = asyncio.get_running_loop()
        stop = asyncio.Event()
        loop.add_signal_handler(signal.SIGTERM, stop.set)
        loop.add_signal_handler(signal.SIGINT, stop.set)
        try:
            await stop.wait()
        finally:
            await head.stop()

    try:
        asyncio.run(run())
    except KeyboardInterrupt:
        pass
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="ray_tpu")
    parser.add_argument("--session-dir", default="",
                        help="session directory (default: newest live)")
    sub = parser.add_subparsers(dest="cmd", required=True)
    p_start = sub.add_parser("start")
    p_start.add_argument("--head", action="store_true")
    # SUPPRESS: without it the subparser's default would clobber a
    # --session-dir passed before the subcommand.
    p_start.add_argument("--session-dir", dest="session_dir",
                         default=argparse.SUPPRESS,
                         help="where session.json lands")
    p_start.add_argument("--die-with-parent", action="store_true",
                         help="SIGKILL the head when its spawner dies "
                              "(test harnesses; operators omit it)")
    p_start.add_argument("--address", default="",
                         help="join an existing head at host:port")
    p_start.add_argument("--num-cpus", type=float,
                         default=float(os.cpu_count() or 1))
    p_start.add_argument("--num-tpus", type=float, default=None)
    sub.add_parser("status")
    p_stop = sub.add_parser("stop")
    p_stop.add_argument("--force", action="store_true",
                        help="SIGKILL instead of SIGTERM")
    p_list = sub.add_parser("list")
    p_list.add_argument("kind", choices=[
        "nodes", "workers", "actors", "placement_groups", "tasks"])
    sub.add_parser("metrics")
    p_logs = sub.add_parser("logs")
    p_logs.add_argument("worker_id", nargs="?", default="",
                        help="worker id hex prefix (>=12 chars); omit "
                             "to list available log files")
    p_logs.add_argument("--bytes", type=int, default=65536)
    p_tl = sub.add_parser("timeline")
    p_tl.add_argument("output", nargs="?", default="timeline.json")
    sub.add_parser("dashboard")
    p_serve = sub.add_parser("serve")
    serve_sub = p_serve.add_subparsers(dest="serve_cmd", required=True)
    p_sdeploy = serve_sub.add_parser("deploy")
    p_sdeploy.add_argument("config_file")
    p_srun = serve_sub.add_parser("run")
    p_srun.add_argument("import_path")
    p_srun.add_argument("--name", default="default")
    p_srun.add_argument("--route-prefix", default="/")
    serve_sub.add_parser("status")
    serve_sub.add_parser("config")
    serve_sub.add_parser("shutdown")
    p_job = sub.add_parser("job")
    job_sub = p_job.add_subparsers(dest="job_cmd", required=True)
    p_submit = job_sub.add_parser("submit")
    p_submit.add_argument("entrypoint")
    p_submit.add_argument("--working-dir", default=None)
    for name in ("status", "logs", "stop"):
        p = job_sub.add_parser(name)
        p.add_argument("job_id")
    job_sub.add_parser("list")
    args = parser.parse_args(argv)

    if args.cmd == "start":
        return _cmd_start(args)
    info = _find_session(args.session_dir)
    if args.cmd == "stop":
        # Reference: ``ray stop``. SIGTERM lets the head persist state
        # and reap its workers (the child-subreaper takes orphans down
        # with it); the session file is then stale by liveness check.
        import signal as _signal

        sig = _signal.SIGKILL if args.force else _signal.SIGTERM
        try:
            os.kill(info["pid"], sig)
        except ProcessLookupError:
            # Exited between the session liveness check and the signal:
            # the desired end state already holds.
            print(f"head (pid {info['pid']}) already stopped")
            return 0
        except OSError as e:
            print(f"head pid {info['pid']}: {e}")
            return 1
        from ._private.utils import process_exited

        deadline = _time.time() + 15
        while _time.time() < deadline:
            if process_exited(info["pid"]):
                break
            _time.sleep(0.1)
        else:
            print(f"head pid {info['pid']} still shutting down "
                  "(state persists on exit); --force to SIGKILL")
            return 1
        print(f"stopped head (pid {info['pid']}, "
              f"session {info['session_dir']})")
        return 0
    if args.cmd == "job":
        from .job_submission import JobSubmissionClient

        client = JobSubmissionClient(info["head_sock"])
        if args.job_cmd == "submit":
            renv = ({"working_dir": args.working_dir}
                    if args.working_dir else None)
            print(client.submit_job(entrypoint=args.entrypoint,
                                    runtime_env=renv))
        elif args.job_cmd == "status":
            print(json.dumps(client.get_job_info(args.job_id), indent=1))
        elif args.job_cmd == "logs":
            print(client.get_job_logs(args.job_id), end="")
        elif args.job_cmd == "stop":
            print(client.stop_job(args.job_id)["status"])
        elif args.job_cmd == "list":
            print(json.dumps(client.list_jobs(), indent=1))
        return 0
    rt = _connect(info)
    try:
        if args.cmd == "serve":
            return _cmd_serve(args)
        if args.cmd == "status":
            summary = rt.state("summary")
            print(f"session: {info['session_dir']}")
            if info.get("dashboard_url"):
                print(f"dashboard: {info['dashboard_url']}")
            for k, v in summary.items():
                print(f"  {k}: {v}")
        elif args.cmd == "list":
            print(json.dumps(rt.state(args.kind), indent=1, default=str))
        elif args.cmd == "metrics":
            print(rt.metrics_text(), end="")
        elif args.cmd == "logs":
            from .core.worker import CoreWorker

            out = CoreWorker.current().head_call(
                "worker_log", {"worker_id": args.worker_id,
                               "bytes": args.bytes})
            if "files" in out:
                print("\n".join(out["files"]))
            else:
                print(out["data"], end="")
        elif args.cmd == "timeline":
            events = rt.timeline(format="chrome")
            with open(args.output, "w") as f:
                json.dump(events, f)
            print(f"wrote {len(events)} events to {args.output}")
        elif args.cmd == "dashboard":
            print(rt.dashboard_url() or "dashboard disabled")
    finally:
        rt.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
