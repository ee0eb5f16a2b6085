"""Reading a traced program (a jaxpr) for what a start-up gauge counts."""
from __future__ import annotations

from typing import List, Sequence

import numpy as np


def indexed_ops(jaxpr, n_indices: int, skip: Sequence[str] = ()
                ) -> List[str]:
    """The gather / scatter equations of ``jaxpr`` (nested jaxprs
    included; equations of the primitives in ``skip`` left out, bodies
    and all) that take ``n_indices`` index vectors or more: on the chip
    each such vector is one serial address computation, so an equation
    of that many is a loop over them whatever else the program does. A
    row read of a table (one index, a row-long slice) is not one."""
    found = []
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name in skip:
            continue
        if name == "gather" or name.startswith("scatter"):
            if int(np.prod(eqn.invars[1].aval.shape[:-1])) >= n_indices:
                found.append(name)
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    found += indexed_ops(inner, n_indices, skip)
    return found
