"""Driver/executor cluster runtime.

(reference: Plugin.scala — RapidsDriverPlugin :463 / RapidsExecutorPlugin
:610, driver<->executor RPC :469-504, shuffle heartbeats
RapidsShuffleHeartbeatManager.scala:33.) TPU-first shape: the one
TPU client lives in the DRIVER process (a chip belongs to one process), so
executors supply host-side parallelism — parquet/text decode, shuffle
file IO — and ship Arrow IPC bytes back; device work stays with the
driver's chip. Liveness is heartbeat-based with task re-execution on
executor loss (the lineage/retry model of §5.3).
"""
from .driver import ClusterManager, ExecutorLostError  # noqa: F401
