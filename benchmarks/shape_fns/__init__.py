"""Operations and bytes of a program, computed from the cell's shapes:
``flops_and_bytes(cell) -> (flops, bytes)`` for one execution."""
