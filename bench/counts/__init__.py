"""Operations and bytes that the work of a call needs, from its shapes."""
