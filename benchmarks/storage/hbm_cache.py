"""Tables held in HBM through create_dataframe(t).cache(): the in-memory
table scan of an interactive deployment."""


class Storage:
    def __init__(self, session, spec, work_dir):
        self.session, self.frames = session, {}

    def load(self, tables):
        self.frames = {name: self.session.create_dataframe(t).cache()
                       for name, t in tables.items()}

    def scans(self):
        return self.frames

    def close(self):
        self.frames = {}
