"""One persistent XLA compile cache, placeable from outside.

Every chip-tool call starts a fresh machine, and within one call the
trainer, the orchestrated trainer and the server are separate
processes: without a shared persistent cache each of them compiles
everything again. The directory is part of the cache key's lookup, so
it must not move between runs:

  - `JAX_COMPILATION_CACHE_DIR` set: JAX reads it itself and this
    module sets NO directory in code — whoever set the variable owns
    the placement (the chip tool, `chip_smoke.py`, an operator);
  - unset, on an accelerator: one fixed path inside the checkout,
    derived from where this package is installed — never from a temp
    name, a pid or a clock.

A job started through `stpu launch --infra local` runs from a synced
copy whose path carries the cluster name, so there the in-checkout
default would move with it: the launcher hands such jobs its own
`JAX_COMPILATION_CACHE_DIR` (client/cli.py `_build_task`).

Called by `train_lm.main`, `inference.runtime.build_runtime` and
`bench.py`, before the first compile. Importing this module does not
import JAX (chip_smoke.py's parent reads `default_dir()` and must stay
off the chip).
"""
from __future__ import annotations

import os
from typing import Optional

ENV_VAR = 'JAX_COMPILATION_CACHE_DIR'


def default_dir() -> str:
    """`<checkout>/.jax_cache` (git-ignored), from the package's own
    location."""
    package_root = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    return os.path.join(package_root, '.jax_cache')


def configure() -> Optional[str]:
    """Turn the persistent compile cache on; returns the directory in
    use, or None when there is none.

    With the variable unset and the CPU backend (the tests, `--cpu`
    dev runs) no directory is set: XLA:CPU caches machine-specific AOT
    code, the checkout gets copied between machines (the chip tool
    does exactly that), and a CPU compile is seconds anyway."""
    import jax
    cache_dir = os.environ.get(ENV_VAR)
    if not cache_dir:
        if jax.default_backend() == 'cpu':
            return None
        cache_dir = default_dir()
        jax.config.update('jax_compilation_cache_dir', cache_dir)
    # The default threshold (1 s) keeps the serving engine's many
    # small shapes (page scatter/gather, first-token sampling, short
    # prefill tails) out of the cache; each is cheap alone, together
    # they are most of a warm start.
    jax.config.update('jax_persistent_cache_min_compile_time_secs', 0.0)
    return cache_dir
