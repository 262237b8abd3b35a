from repro.utils.tree import (
    tree_size_bytes,
    tree_param_count,
    tree_map_with_name,
    flatten_names,
)
