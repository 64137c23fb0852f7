from repro.checkpoint.io import (load_metadata, load_pytree,  # noqa: F401
                                 place_flat, read_flat, save_pytree)
