"""Operations and bytes the benchmark's work needs, computed from shapes."""
