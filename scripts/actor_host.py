#!/usr/bin/env python
"""Actor-host entry point for cross-host dataflow fragments
(ddls_tpu/rl/fragments.py): connect to the learner's listener, build
the vec env + deferred-fetch collector from its CONFIG frame, then
serve PARAMS -> SEGMENT -> ACK until SHUTDOWN.

Spawned by ``LearnerFragment`` (train/loops.py
``collect_transport='socket'``) or run by hand against a remote
learner:

    python scripts/actor_host.py --connect tcp:10.0.0.2:7000

Actor hosts are HOST collectors: jax is pinned to CPU before its first
op unless ``--allow-device`` is given (the learner that spawned them may
hold the accelerator, which belongs to one process). SIGTERM exits through ``finally`` so the env workers and
shm slabs are reclaimed — the kill-teardown test pins zero litter.
"""
import argparse
import os
import signal
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--connect", required=True,
                        help="learner address: unix:<path> or "
                             "tcp:<host>:<port>")
    parser.add_argument("--allow-device", action="store_true",
                        help="let jax pick an accelerator backend "
                             "(default: pin to CPU — actors are host "
                             "collectors)")
    parser.add_argument("--connect-timeout-s", type=float, default=30.0)
    args = parser.parse_args()

    if not args.allow_device:
        from ddls_tpu.utils.runtime import pin_cpu_platform

        pin_cpu_platform()

    # a clean SystemExit unwinds through serve()'s blocking recv and
    # runs the finally-cleanup below (vec-env workers, shm slabs, fd)
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))

    from ddls_tpu.rl.fragments import ActorHostDriver, connect_address

    sock = connect_address(args.connect, timeout_s=args.connect_timeout_s)
    driver = ActorHostDriver(sock)
    try:
        driver.serve()
    finally:
        driver.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
