# The paper's primary contribution, adapted to JAX/TPU: a single-source
# performance-portability core. One canonical op name -> {reference jnp,
# Pallas TPU} lowerings selected by a policy switch (the PHAST macro
# analogue), PHAST-style containers (Blob) and functors.
from repro.core.container import Blob, MajorOrder, as_layout
from repro.core.functor import (
    for_each_elementwise,
    for_each_rows,
    for_each_tiles,
    matrix_plus_vector_rows,
)
from repro.core.policy import (
    Backend,
    current_backend,
    interpret_default,
    on_tpu,
    set_default_backend,
    use_backend,
)
from repro.core.registry import (
    OpEntry,
    attach_pallas,
    clear_tuning,
    coverage,
    dispatch,
    get_op,
    get_tuning,
    last_resolved,
    list_ops,
    last_plan,
    load_tuning_table,
    register_op,
    resolved_plans,
    set_tuning,
    tuning_overrides,
    tuning_table,
)

__all__ = [
    "Blob",
    "MajorOrder",
    "as_layout",
    "Backend",
    "current_backend",
    "interpret_default",
    "on_tpu",
    "set_default_backend",
    "use_backend",
    "OpEntry",
    "attach_pallas",
    "clear_tuning",
    "coverage",
    "dispatch",
    "get_op",
    "get_tuning",
    "last_resolved",
    "list_ops",
    "last_plan",
    "load_tuning_table",
    "register_op",
    "resolved_plans",
    "set_tuning",
    "tuning_overrides",
    "tuning_table",
    "for_each_elementwise",
    "for_each_rows",
    "for_each_tiles",
    "matrix_plus_vector_rows",
]
