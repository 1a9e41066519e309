"""Reading compiled HLO text in tests."""
import re
from typing import List, Sequence


def whole_pool_slices(hlo: str, pool_shape: Sequence[int]) -> List[str]:
    """Lines of compiled ``hlo`` where a dynamic-slice or a
    dynamic-update-slice produces or consumes one layer's whole page
    pool: ``pool_shape`` (P, Hkv, page, D), alone or behind a unit block
    axis. The stacked (num_blocks, P, ...) pools match neither."""
    shape = re.compile(r"\[(?:1,)?%s\]" % ",".join(map(str, pool_shape)))
    return [line for line in hlo.splitlines()
            if ("dynamic-slice" in line or "dynamic-update-slice" in line)
            and shape.search(line)]


def whole_pool_copies(hlo: str, pool_shape: Sequence[int]) -> List[str]:
    """Lines of compiled ``hlo`` where a ``copy`` produces a whole page
    pool ``pool_shape`` (P, Hkv, page, D), one layer's or stacked behind
    a block axis: a relayout of the pool."""
    shape = re.compile(r"= bf16\[(?:\d+,)?%s\]\{[^}]*\} copy\("
                       % ",".join(map(str, pool_shape)))
    return [line for line in hlo.splitlines() if shape.search(line)]
