"""Backend compiles (persistent-cache fetches included) inside the window.

Counted by a ``jax.monitoring`` listener on
``/jax/core/compile/backend_compile_duration`` from the window's first to
its last step, in the traced run.  Warm-up compiles every shape the
traffic can reach, so anything counted here is a shape it missed or a
program the prefill LRU evicted and rebuilt.
"""


def read(run):
    return run.compiles_in_window
