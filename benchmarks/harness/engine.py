"""The system under test, driven as its users drive it. The only file of the
benchmark that imports the program: TpuSession(conf) ->
workloads.tpch.queries()[n](tables), a fresh tree for every execution ->
DataFrame.to_arrow()."""
import importlib

import jax

HOST_OPERATORS = ("HostProjectExec", "HostFilterExec")


def _plan_nodes(node):
    yield node
    for child in list(node.children) + list(getattr(node, "members", [])):
        yield from _plan_nodes(child)


class System:
    def __init__(self, config, traffic, work_dir):
        import spark_rapids_tpu as st
        from spark_rapids_tpu.workloads import tpch
        self.traffic = traffic
        self.session = st.TpuSession(dict(config["conf"]))
        self.queries = [tpch.queries()[q["query"]] for q in traffic["queries"]]
        self.storage = importlib.import_module(
            "benchmarks.storage." + config["storage"]["kind"]).Storage(
                self.session, config["storage"], work_dir)
        self.hidden = {"host_operators": 0, "degraded_to_host": 0}

    def native(self):
        """Whether the program's C++ host library was built here."""
        from spark_rapids_tpu.utils.native import native_lib
        return "built" if native_lib() else "absent (numpy paths)"

    def load(self, tables):
        """Put the generated tables where the configuration keeps them."""
        self.storage.load(tables)

    def execute(self, which=0):
        """One execution of the mix's query `which`: a fresh tree, run until
        the host holds the table. What would hide the device is counted over
        every execution, the warm-up's too."""
        with jax.profiler.TraceAnnotation("bench.execution"):
            with jax.profiler.TraceAnnotation("bench.build_tree"):
                df = self.queries[which](self.storage.scans())
            with jax.profiler.TraceAnnotation("bench.to_arrow"):
                out = df.to_arrow()
        self.hidden["host_operators"] += sum(
            type(n).__name__ in HOST_OPERATORS
            for n in _plan_nodes(df._last_root))
        self.hidden["degraded_to_host"] += sum(
            int(m.get("degradedToHost", 0))
            for m in df.last_metrics().values())
        return out

    def counters(self):
        """The program's exact counts so far (profiler/xla_stats.py)."""
        from spark_rapids_tpu.profiler import xla_stats
        snap = xla_stats.snapshot()
        return {k: snap[k] for k in ("compiles", "cache_hits", "dispatches")}

    def off_device(self):
        """What would have hidden the device, summed over every execution so
        far: 0 each."""
        from spark_rapids_tpu.runtime import program_cache
        return dict(self.hidden, background_compile_failures=int(
            program_cache.stats()["program_cache_background_failures"]))

    def close(self):
        """Free the program's state: cached tables, session, files."""
        self.storage.close()
        self.session.stop()
