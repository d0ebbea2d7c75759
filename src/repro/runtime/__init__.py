from repro.runtime.fault import (  # noqa: F401
    FailureInjector, SimulatedFailure, Watchdog, run_with_restarts,
)

# compression imports JAX; core/ imports this package (``spans``) and stays
# importable without JAX, so its names load on first use
_COMPRESSION = ("make_compressed_grad_fn", "quantized_allreduce",
                "tree_quantized_allreduce")


def __getattr__(name):
    if name in _COMPRESSION:
        from repro.runtime import compression
        return getattr(compression, name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
