"""Each table one Parquet file, written once in set-up and read anew by
session.read.parquet(path) in every execution: the path of a Spark job
reading a lake. Nothing is cached between executions."""
import os

import pyarrow.parquet as pq


class Storage:
    def __init__(self, session, spec, work_dir):
        self.session, self.spec, self.work_dir = session, spec, work_dir
        self.files = {}

    def load(self, tables):
        os.makedirs(self.work_dir, exist_ok=True)
        for name, table in tables.items():
            self.files[name] = os.path.join(self.work_dir, name + ".parquet")
            pq.write_table(table, self.files[name],
                           compression=self.spec["compression"],
                           row_group_size=self.spec["row_group_rows"])

    def scans(self):
        return {name: self.session.read.parquet(path)
                for name, path in self.files.items()}

    def close(self):
        for path in self.files.values():
            if os.path.exists(path):
                os.remove(path)
        self.files = {}
