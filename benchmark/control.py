"""The control: the plain reference without conntrack, put in the
program's place (``PERF.md`` §2).

For a system that states no precision, the control breaks one
guarantee the configuration states: replies and established flows are
answered by policy alone, as if no conntrack entry existed.  It is
installed through ``run.main``'s hook, under the serving lane, so its
answers go through the same tickets, the same comparison and the same
``checks`` as the program's, and the run has to come out not correct.
The program still runs each launch; only its verdicts are replaced.

It answers with the run's own reference class: for a kind that brings
a reference of its own, pass that class (``parts.Reference``, as
``run.main`` is given ``parts``), so the control breaks that kind's
guarantee:
``hook=functools.partial(control.install, Reference=parts.Reference)``.
"""

import numpy as np

import reference


def install(system, dep, Reference=reference.Reference):  # noqa: N803
    ref = Reference(dep)

    def wrap(step):
        def control(packed, now=None, payload=None):
            _v, event, _i, nat = step(packed, now, payload)
            rec = {f: np.asarray(packed[k]) for k, f in enumerate(fields)}
            verdict, ident = ref.policy_only(rec)
            return verdict.astype(np.int32), event, \
                ident.astype(np.int32), nat
        return control

    fields = system.wrap_step(wrap)
