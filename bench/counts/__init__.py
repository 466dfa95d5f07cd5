"""Operations and bytes of the benchmark's kernels and steps, from shapes."""
