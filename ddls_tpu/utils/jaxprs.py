"""Reading a traced program (a jaxpr) for what a start-up gauge counts."""
from __future__ import annotations

from typing import List, Sequence

import numpy as np


def equations(jaxpr, skip: Sequence[str] = ()):
    """Every equation of ``jaxpr``, nested jaxprs included (a ``scan``'s
    body, a ``cond``'s branches, a ``pjit``'s callee); equations of the
    primitives in ``skip`` are left out, bodies and all."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in skip:
            continue
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from equations(inner, skip)


def indexed_ops(jaxpr, n_indices: int, skip: Sequence[str] = ()
                ) -> List[str]:
    """The gather / scatter equations of ``jaxpr`` (nested jaxprs
    included; equations of the primitives in ``skip`` left out, bodies
    and all) that take ``n_indices`` index vectors or more: on the chip
    each such vector is one serial address computation, so an equation
    of that many is a loop over them whatever else the program does. A
    row read of a table (one index, a row-long slice) is not one."""
    return [eqn.primitive.name for eqn in equations(jaxpr, skip)
            if (eqn.primitive.name == "gather"
                or eqn.primitive.name.startswith("scatter"))
            and int(np.prod(eqn.invars[1].aval.shape[:-1])) >= n_indices]
