"""Executor worker process.

(reference: RapidsExecutorPlugin, Plugin.scala:610 — init, heartbeat
endpoint, task hooks.) Each executor is a separate OS process that
connects back to the driver, registers, then serves tasks over one
socket while a daemon thread heartbeats on a second. Tasks are pickled
callables returning picklable results (host-side work only — the TPU
client lives in the driver; JAX stays unimported here unless a task
pulls it in, and then it is forced onto the CPU platform).
"""
from __future__ import annotations

import os
import socket
import sys
import threading
import time
import traceback

from .rpc import RpcClosed, recv_msg, send_msg

__all__ = ["executor_main"]

HEARTBEAT_PERIOD_S = 0.5


def _heartbeat_loop(host: str, port: int, exec_id: int, stop):
    try:
        hb = socket.create_connection((host, port))
        send_msg(hb, "hb_register", {"executor": exec_id,
                                     "pid": os.getpid()})
        while not stop.is_set():
            send_msg(hb, "heartbeat", {"executor": exec_id,
                                       "ts": time.time()})
            stop.wait(HEARTBEAT_PERIOD_S)
    except OSError:
        pass  # driver gone; the task loop will exit too


def executor_main(host: str, port: int, exec_id: int) -> None:
    # any accidental JAX usage inside a task must not grab the TPU
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    # Pin the platform via jax.config too, before any task runs a
    # query fragment: the driver process holds the chip, and a child
    # that asked for it would fail or hang. SRTPU_EXECUTOR_PLATFORM=tpu
    # opts an executor into a chip of its own on hosts that have one.
    platform = os.environ.get("SRTPU_EXECUTOR_PLATFORM", "cpu")
    try:
        import jax
        jax.config.update("jax_platforms", platform)
    except ImportError:
        pass
    stop = threading.Event()
    t = threading.Thread(target=_heartbeat_loop,
                         args=(host, port, exec_id, stop), daemon=True,
                         name="tpu-exec-hb")
    t.start()
    sock = socket.create_connection((host, port))
    send_msg(sock, "register", {"executor": exec_id, "pid": os.getpid()})
    try:
        while True:
            kind, payload = recv_msg(sock)
            if kind == "shutdown":
                break
            if kind != "task":
                send_msg(sock, "error", {"message": f"bad kind {kind}"})
                continue
            task_id = payload["task_id"]
            try:
                from ..runtime import faults
                if faults.ACTIVE:
                    # executor.task: raise fails the task (reported,
                    # driver-side retry policy applies), kill exits the
                    # PROCESS — the heartbeat/socket loss path marks
                    # this executor lost and requeues its tasks
                    faults.hit("executor.task")
                fn = payload["fn"]
                args = tuple(payload.get("args", ()))
                # tasks submitted with tables=... get them appended as
                # the final positional argument — ALWAYS when the flag
                # is set, so an empty bucket list doesn't change arity
                if payload.get("has_tables"):
                    args = args + (payload.get("_arrow", []),)
                result = fn(*args)
                # metric snapshots the task recorded (fragment op
                # metrics) ride the result frame back to the driver —
                # without this, executor MetricSets die with the process
                from .task_metrics import drain_task_metrics
                tm = drain_task_metrics()
                extra = {"task_metrics": tm} if tm else {}
                from .rpc import ArrowResult
                if isinstance(result, ArrowResult):
                    send_msg(sock, "result",
                             {"task_id": task_id, "value": result.meta,
                              "arrow_result": True, **extra},
                             tables=result.tables)
                else:
                    send_msg(sock, "result", {"task_id": task_id,
                                              "value": result, **extra})
            except BaseException as e:  # report, don't die
                # drain partial metric records so they can't leak into
                # the NEXT task's result frame
                from .task_metrics import drain_task_metrics
                drain_task_metrics()
                payload = {"task_id": task_id, "message": repr(e),
                           "traceback": traceback.format_exc()}
                from ..runtime.faults import InjectedFault
                from .blocks import FetchFailed
                if isinstance(e, FetchFailed):
                    # structured fields survive the wire so the driver
                    # re-raises a typed FetchFailed (lineage targeting
                    # without exception-text parsing)
                    payload["error_fields"] = {
                        "type": "FetchFailed",
                        "addr": list(e.addr) if e.addr else None,
                        "shuffle_id": e.shuffle_id}
                elif isinstance(e, InjectedFault):
                    # ditto for injections: the driver rebuilds the
                    # type so transient-error classification survives
                    # the process boundary
                    payload["error_fields"] = {
                        "type": "InjectedFault", "point": e.point}
                send_msg(sock, "error", payload)
    except RpcClosed:
        pass
    finally:
        stop.set()


if __name__ == "__main__":
    executor_main(sys.argv[1], int(sys.argv[2]), int(sys.argv[3]))
