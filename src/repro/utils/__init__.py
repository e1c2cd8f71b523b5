from repro.utils.tree import (  # noqa: F401
    tree_bytes,
    tree_count,
    tree_cast,
    tree_zeros_like,
)
