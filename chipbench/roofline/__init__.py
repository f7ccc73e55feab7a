"""Operations and bytes of each kernel, from its shapes."""
