"""Find a part of the benchmark by its name: ``<dir>/<name>.py``."""

import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def module(kind: str, name: str):
    path = os.path.join(HERE, kind, f"{name}.py")
    tag = "".join(c if c.isalnum() else "_" for c in f"{kind}_{name}")
    spec = importlib.util.spec_from_file_location(tag, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
