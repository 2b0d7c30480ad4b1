"""Standalone shard worker: run ONE serving-mesh shard on this host and
wait for a router to dial in.

    python -m repro.launch.shard_worker --host 0.0.0.0 --port 7070

Then, from the router process (any machine that can reach this one):

    mesh = MultiProcessServingEngine(...).start()
    mesh.connect_shard("hostB:7070")

The worker carries NO configuration of its own — the router's ``hello``
frame ships the shard id, batcher config and session budget, so the
same worker binary serves any mesh. With ``--forever`` the worker
outlives its router: serving state (weights, warm jit cache, session
carries) persists across connections, which is how a crashed router —
or a mesh re-adopting this shard after a network partition
(``awaiting_rejoin``) — resumes where it left off.
"""

from __future__ import annotations

import argparse
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="repro.launch.shard_worker",
        description="serve one mesh shard on this host (see module "
                    "docstring for the remote-join recipe)")
    ap.add_argument("--host", default="0.0.0.0",
                    help="interface to bind (default 0.0.0.0)")
    ap.add_argument("--port", type=int, default=0,
                    help="port to bind (default 0 = ephemeral; the "
                         "bound port is printed either way)")
    ap.add_argument("--forever", action="store_true",
                    help="keep serving across router connections "
                         "instead of exiting after the first one")
    ap.add_argument("--state-dir", default=None, metavar="DIR",
                    help="DurableStore root (shared with the router's "
                         "--state-dir): a cold worker restart primes "
                         "its weight replicas from the last good "
                         "checkpoint before the router re-adopts it")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving.transport import serve_shard

    enable_compile_cache()

    def _report(port: int) -> None:
        # machine-greppable: launch scripts scrape the bound port
        print(f"shard-worker listening on {args.host}:{port}",
              flush=True)

    try:
        serve_shard(args.host, args.port, forever=args.forever,
                    on_bound=_report, state_dir=args.state_dir)
    except KeyboardInterrupt:
        pass
    return 0


if __name__ == "__main__":
    sys.exit(main())
