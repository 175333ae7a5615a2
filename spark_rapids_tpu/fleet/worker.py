"""Fleet worker: one real service process in the fabric, the operator's
entry point (docs/fleet.md).

`python -m spark_rapids_tpu.fleet.worker` builds a session, registers
the shared parquet views, starts the gateway (which joins the fleet
named by --fleet-dir), prints one READY line with its addresses, and
serves until stdin closes. Each worker is a real interpreter with a
cold program cache, not a forked copy of a warm parent.
"""
from __future__ import annotations

import argparse
import json
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="spark_rapids_tpu.fleet.worker")
    ap.add_argument("--fleet-dir", required=True,
                    help="peer directory root (shared across workers)")
    ap.add_argument("--view", action="append", default=[],
                    metavar="NAME=PARQUET_PATH",
                    help="register a parquet path as a temp view")
    ap.add_argument("--conf", action="append", default=[],
                    metavar="KEY=VALUE", help="extra session conf")
    args = ap.parse_args(argv)

    from .. import TpuSession
    from ..config import FLEET_DIRECTORY, RESULT_CACHE_ENABLED
    s = TpuSession()
    s.set_conf(FLEET_DIRECTORY.key, args.fleet_dir)
    s.set_conf(RESULT_CACHE_ENABLED.key, True)
    for kv in args.conf:
        k, _, v = kv.partition("=")
        s.set_conf(k, v)
    for kv in args.view:
        name, _, path = kv.partition("=")
        s.read.parquet(path).create_or_replace_temp_view(name)

    srv = s.serve()
    member = getattr(s, "_fleet_member", None)
    ready = {"host": srv.host, "port": srv.port,
             "peer_id": member.peer_id if member else None,
             "warm": getattr(member, "warm_summary", None)}
    sys.stdout.write("READY " + json.dumps(ready) + "\n")
    sys.stdout.flush()

    # serve until the parent closes our stdin — no
    # signal handling needed, and an orphaned worker exits on its own
    for _line in sys.stdin:
        if _line.strip() == "stop":
            break
    try:
        if member is not None:
            member.leave()
        srv.close()
        s.stop()
    except Exception:
        pass
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
