"""Tables held in the HBM of every chip of a mesh: create_dataframe(t).cache()
in a session whose conf says mesh.devices, as `hbm_cache` makes it, and then
the configuration's guarantee: every one of the `chips` devices holds rows of
every table. A program that keeps a cached table on one chip fails here, at
load, and not after minutes of compiling."""
from benchmarks.storage import hbm_cache


class Storage(hbm_cache.Storage):
    def __init__(self, session, spec, work_dir):
        super().__init__(session, spec, work_dir)
        self.chips = spec["chips"]

    def load(self, tables):
        super().load(tables)
        for name, frame in self.frames.items():
            where = getattr(frame, "cached_devices", None)
            if where is None:
                raise RuntimeError(
                    f"{name}: the cached DataFrame cannot say which devices "
                    "hold its rows (no cached_devices()): this program does "
                    "not shard a cached table over the mesh")
            held = where()
            if len(held) != self.chips:
                raise RuntimeError(
                    f"{name}: rows on {len(held)} device(s) "
                    f"{[str(d) for d in held]}, the configuration says every "
                    f"one of {self.chips} chips holds a share")
