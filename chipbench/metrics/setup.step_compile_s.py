"""Host seconds JAX spent in set-up tracing, lowering, and compiling or
loading from its compile cache the step program (``hiertrain_step``):
the first of the step's compile records that the program keeps
(``repro.obs``), which is set-up's.  The load from the cache happens
inside the backend compile, so it is not added again.  None where the
program keeps no such records or none of the step."""
from chipbench.scopes import program_obs

STEP = "hiertrain_step"
EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
          "/jax/core/compile/jaxpr_to_mlir_module_duration",
          "/jax/core/compile/backend_compile_duration")


def read(rec, tr):
    obs = program_obs()
    compiles = obs.snapshot().get(STEP) if obs is not None else None
    if not compiles:
        return None
    return sum(compiles[0].get(e, 0.0) for e in EVENTS)
