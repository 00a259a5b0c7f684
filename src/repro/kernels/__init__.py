"""Pallas TPU kernels for the MIPS hot spots: compiled by Mosaic on the TPU,
run in interpret mode on the CPU backend (``common.resolve_interpret``).

  mips_topk    — tiled exact-MIPS linear scan + streaming top-k (MXU)
  gather_score — scalar-prefetch fused row-gather + dot (beam expansion)
  topk_merge   — in-VMEM candidate-pool merge (Algorithm 1 line 7-8)
  beam_step    — fused full Algorithm-1 iteration (select + gather + dedup +
                 score + merge in VMEM); the "pallas" walk backend (DESIGN §3)
  commit_merge — fused reverse-link top-M merge of the Algorithm-2 batched
                 commit (bucket + gather + rescore + dedup + rank per target
                 tile in VMEM); the "pallas" commit backend (DESIGN §7)
  quant_score  — fused int8 row-gather + dequant + dot (1-byte DMA, fp32
                 rescale in VMEM); the gathered scorer of the "int8"
                 storage backend (DESIGN §8)
"""
