"""What each staged program of a fit needs of the device's memory.

Printed when a cell's set-up runs out of device memory: the rows of a
configuration are fixed, so a step that does not fit fails the run, and
this report says which step and by how much."""

from __future__ import annotations

import sys


def report(run) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding
    from repro.core import FusionContext

    regions = getattr(run.algo, "regions", None)
    if regions is None:
        return
    ctx = FusionContext(mode=run.config["mode"], pallas=run.config["pallas"])
    one = SingleDeviceSharding(run.devs[0])
    for label, region, shapes, differentiated in regions(run.config):
        sds = [jax.ShapeDtypeStruct(s, jnp.float32) for s in shapes]
        planned = region.trace(*sds).plan(context=ctx)
        compiled = planned.compile()
        stages = [(label, compiled._cplan)]
        if differentiated:
            stages.append((label + ".grad", compiled._get_bwd()[0]))
        for name, cp in stages:
            args = [jax.ShapeDtypeStruct(tuple(nd.shape), jnp.float32,
                                         sharding=one)
                    for nd in cp.plan.graph.inputs()]
            fn, _raw = cp.staged_callable()
            try:
                ma = fn.lower(*args).compile().memory_analysis()
                line = (f"args {ma.argument_size_in_bytes} B, out "
                        f"{ma.output_size_in_bytes} B, temp "
                        f"{ma.temp_size_in_bytes} B, alias "
                        f"{ma.alias_size_in_bytes} B")
            except Exception as e:      # noqa: BLE001 — reported, not hidden
                line = f"does not compile: {str(e).splitlines()[0]}"
            print(f"# memory_analysis {name} @ {run.config['rows']} rows: "
                  f"{line}", file=sys.stderr, flush=True)
