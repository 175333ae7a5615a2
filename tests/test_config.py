"""The conf registry holds no dead option, and docs/configs.md is what
`config.generate_docs()` emits."""
import inspect
import pathlib
import re

from spark_rapids_tpu import config

ROOT = pathlib.Path(__file__).resolve().parent.parent
PKG = ROOT / "spark_rapids_tpu"


def test_every_conf_entry_has_a_reader():
    """Each entry is read somewhere under spark_rapids_tpu/ other than
    config.py: by its constant's name, by its key, or through a TpuConf
    property that reads it."""
    engine = "\n".join(p.read_text() for p in sorted(PKG.rglob("*.py"))
                       if p.name != "config.py")
    names = {}
    for name, entry in vars(config).items():
        if isinstance(entry, config.ConfEntry):
            names.setdefault(entry.key, []).append(name)
    props = {name: inspect.getsource(p.fget)
             for name, p in vars(config.TpuConf).items()
             if isinstance(p, property)}

    assert sorted(names) == sorted(config.REGISTRY)

    def read(key):
        consts = names[key]
        via = [p for p, src in props.items()
               if any(re.search(rf"\b{c}\b", src) for c in consts)]
        return any(re.search(rf"\b{re.escape(s)}\b", engine)
                   for s in [key] + consts) \
            or any(re.search(rf"\bconf\.{p}\b", engine) for p in via)

    dead = [key for key in sorted(config.REGISTRY) if not read(key)]
    assert dead == []


def test_configs_md_is_generated():
    assert (ROOT / "docs" / "configs.md").read_text() == \
        config.generate_docs()
