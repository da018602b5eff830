"""repro: 'The Duck's Brain' — in-database NN training/inference, as a
multi-pod JAX framework. See DESIGN.md."""
import os as _os

__version__ = "1.0.0"

# A cache directory named in the environment may be shared with other
# versions of the program, and the benchmark harness turns it on with its
# own code: key it with op metadata here, so that a cached executable
# always carries this version's named scopes.
if _os.environ.get("JAX_COMPILATION_CACHE_DIR"):
    from repro.launch.compile_cache import key_on_metadata as _key

    _key()
