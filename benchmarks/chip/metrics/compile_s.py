"""Seconds of set-up in XLA backend compiles and in loads from the
persistent compilation cache, from JAX's own compile events."""


def read(run):
    r = run.mon.get("setup")
    return r["backend_compile_s"] + r["cache_retrieval_s"]
