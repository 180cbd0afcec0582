"""The program's names as they appear in a device trace (regexes): its
jitted programs (``XLA Modules``, the hash dropped) and its kernels
(``XLA Ops``, the HLO name)."""

# the fused sample -> encode -> count program (`repro.core.fused`)
CHAIN = r"^jit_chain$"
# the arena_commit Pallas kernel inside it (`repro.kernels.commit`)
COMMIT_KERNEL = r"^%arena_commit\b"
# the capacity-shaped arena write that follows it (`repro.core.fused`)
COMMIT_WRITE = r"^jit__commit_write$"
# greedy selection over a bitmap arena (`repro.core.selection`)
SELECT = r"^jit_select_dense$"
# sigma(S) hit counting over a bitmap arena (`repro.core.store`)
HITS = r"^jit__bitmap_hits$"
