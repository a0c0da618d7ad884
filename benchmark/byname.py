"""Find a part of the benchmark by its name: ``<dir>/<name>.py``.

A configuration's ``kind`` brings each role of its cells as a file of
its own under the benchmark's directory, found by the kind's name
(``kind_parts``).  Where a role's file is absent, the role is the
default's:

=============  =============================  ==========================
role           file                           default
=============  =============================  ==========================
deployment     ``deployments/<kind>.py``      none: every kind has one
system         ``systems/<kind>.py``          ``sut.System``
reference      ``references/<kind>.py``       ``reference.Reference``
flows          ``flows/<kind>.py``            ``traffic.FlowSource``
events         ``events/<kind>.py``           none: a mix with
                                              ``"events"`` needs one
=============  =============================  ==========================

Each contract is written in the default's docstring; an events file
provides ``schedule(cfg, mix, dep, seed, seconds)``, which returns
``[(at_s, event), ...]`` for ``run.py`` to play inside the window
through ``system.apply(event)``.  A kind's file may subclass the
default, or load another kind's file with ``module``.
"""

import importlib.util
import os
from typing import NamedTuple, Optional

HERE = os.path.dirname(os.path.abspath(__file__))


def module(kind: str, name: str, root: str = HERE):
    path = os.path.join(root, kind, f"{name}.py")
    tag = "".join(c if c.isalnum() else "_" for c in f"{kind}_{name}")
    spec = importlib.util.spec_from_file_location(tag, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find(kind: str, name: str, root: str = HERE):
    """``module(kind, name, root)``, or None where it has no file."""
    if not os.path.isfile(os.path.join(root, kind, f"{name}.py")):
        return None
    return module(kind, name, root)


class Parts(NamedTuple):
    """A kind's roles, each loaded once: its deployment module
    (``build(cfg, seed)``), its ``System``, ``Reference`` and
    ``FlowSource`` classes, and its events module or None."""

    kind: str
    deployment: object
    System: type
    Reference: type
    FlowSource: type
    events: Optional[object]


def kind_parts(kind: str, root: str = HERE) -> Parts:
    """The roles of ``kind``, found under ``root``."""
    import reference
    import sut
    import traffic

    def role(folder, attr, default):
        mod = find(folder, kind, root)
        return default if mod is None else getattr(mod, attr)

    return Parts(kind, module("deployments", kind, root),
                 role("systems", "System", sut.System),
                 role("references", "Reference", reference.Reference),
                 role("flows", "FlowSource", traffic.FlowSource),
                 find("events", kind, root))
