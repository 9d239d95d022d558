"""§Perf hillclimb driver: lower+compile the three chosen cells under each
optimization variant on the production single-pod mesh, recording
variant-tagged dry-run stats (and flop probes where compute changes).

``--layout auto`` re-runs the arms under the planner-searched layout
(``repro.dist.planner``) instead of the fixed PR-1 sharding rules;
explicit variant keys (``act``, ``serve_params``) still win over the
planner's choices, so each arm measures exactly what it names.

The ``fusion: "gen"`` arm routes the CE loss through the staged fusion
pipeline (``launch/train._fused_lse``: trace → plan → compile once per
shape); since PR 3 its *backward* pass is the planned gradient DAG via
the operator's custom_vjp, so the arm measures generated fused operators
in both directions of the train step.

  PYTHONPATH=src python -m repro.launch.hillclimb [--layout auto]
"""

from __future__ import annotations

import traceback

CELLS = {
    # (arch, shape): [(variant_tag, variant_dict), ...]
    ("yi-34b", "prefill_32k"): [
        ("actdp", {"act": "dp"}),
        ("actsp", {"act": "sp"}),
        ("actdp-servep", {"act": "dp", "serve_params": True}),
    ],
    ("grok-1-314b", "train_4k"): [
        ("actdp", {"act": "dp"}),
        ("actdp-capmoe", {"act": "dp", "moe_impl": "capacity"}),
    ],
    ("olmoe-1b-7b", "train_4k"): [
        ("actdp", {"act": "dp"}),
        ("actdp-capmoe", {"act": "dp", "moe_impl": "capacity"}),
        ("actdp-fusedloss", {"act": "dp", "fusion": "gen"}),
    ],
    # bonus: decode memory/collective lever
    ("yi-34b", "decode_32k"): [
        ("servep", {"serve_params": True}),
        ("servep-gqagrp", {"serve_params": True, "gqa_grouped": True}),
    ],
}

#: variants whose FLOPs/bytes change (need probes, run separately under a
#: small device count): (arch, shape, tag, variant)
PROBE_VARIANTS = [
    ("grok-1-314b", "train_4k", "capmoe", {"moe_impl": "capacity"}),
    ("olmoe-1b-7b", "train_4k", "capmoe", {"moe_impl": "capacity"}),
    ("yi-34b", "decode_32k", "gqagrp", {"gqa_grouped": True}),
]


def main() -> None:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--layout", default="fixed", choices=("fixed", "auto"))
    args = ap.parse_args()

    # the 512-host-device override must land before any jax backend init,
    # so it runs behind the main() guard (merely importing this module
    # must not fork the process's device count)
    from repro.launch import ensure_host_device_count
    ensure_host_device_count(512)

    from repro import compile_cache
    compile_cache.enable()
    from repro.launch.dryrun_lib import run_cell
    from repro.launch.mesh import make_production_mesh

    mesh = make_production_mesh(multi_pod=False)
    for (arch, shape), variants in CELLS.items():
        for tag, variant in variants:
            try:
                fusion = variant.get("fusion", "off")
                rec = run_cell(arch, shape, mesh, "pod16x16",
                               fusion=fusion, variant=variant,
                               variant_tag=tag, layout=args.layout)
                coll = rec["collective_bytes_per_device_trip_corrected"]
                print(f"OK   {arch} × {shape} [{tag}]: "
                      f"coll/dev={coll['total']:.3e} "
                      f"rawflops={rec['flops_per_device']:.3e} "
                      f"rawbytes={rec['bytes_per_device']:.3e}",
                      flush=True)
            except Exception as e:
                print(f"FAIL {arch} × {shape} [{tag}]: "
                      f"{type(e).__name__}: {e}", flush=True)
                traceback.print_exc()


if __name__ == "__main__":
    main()
