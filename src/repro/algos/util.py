"""Small shared helpers for the algorithm suite."""

import numpy as np

from repro import obs


def fs(x) -> float:
    """Python float from any single-element array (fused ops return (1,1)).

    A blocking device-to-host read, under a ``repro.sync`` span; the
    reshape runs on the host copy, so the read dispatches no device op."""
    with obs.span(obs.SYNC):
        return float(np.asarray(x).reshape(()))
