from .ops import (
    count_matches,
    find_pattern_mask,
    find_pattern_mask_batch,
    find_pattern_mask_rowgroup,
    find_pattern_masks_multi,
    find_pattern_positions,
)

__all__ = ["find_pattern_mask", "find_pattern_mask_batch",
           "find_pattern_mask_rowgroup", "find_pattern_masks_multi",
           "find_pattern_positions", "count_matches"]
