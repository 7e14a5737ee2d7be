"""Operations and bytes a program needs, computed from shapes: the
algorithmic minimum, never the compiler's count."""
