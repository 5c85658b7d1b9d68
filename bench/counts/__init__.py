"""Operations and bytes that the shares divide by, computed from shapes,
one module per thing counted."""
