"""The one place the persistent XLA compile cache is located.

``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself at import — that
directory, as given, and nothing is configured in code. Unset: the fixed
``<checkout>/.jax_cache``. The installed JAX keys every entry by the
lowered module, the jaxlib and backend versions, the XLA flags and the
device topology (for XLA:CPU that includes the host's CPU feature list),
so a changed kernel or a foreign host misses instead of loading a stale
entry; no sub-directory of our own is needed.
"""

from __future__ import annotations

import os

_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)


def configure_compile_cache() -> str:
    """Point JAX's persistent compile cache and return its directory.
    Call before the first compile of the process."""
    given = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if given:
        return given
    import jax

    jax.config.update("jax_compilation_cache_dir", _DEFAULT_DIR)
    return _DEFAULT_DIR
