"""The llama shapes the report tools (``plan_report``, ``sharding_report``,
``tune_report``) name by ``--preset``."""

PRESETS = {
    # dim, n_layers, n_heads, n_kv_heads, vocab, mlp_ratio.
    # TransformerConfig.mlp_hidden applies the SwiGLU 2/3 factor, so
    # hidden = 2*ratio*dim/3 (rounded to 128): the published Llama hidden
    # sizes need ratio 5.25 (8B: 14336 = 2*5.25*4096/3) and 6.0
    # (3.2-1B: 8192 = 2*6*2048/3).
    "tiny": (256, 8, 8, 4, 1024, 4.0),
    # ~200M params: big enough for meaningful attention/window timings at
    # long seq, small enough to compile quickly.
    "small": (1024, 12, 16, 8, 32000, 4.0),
    "1b": (2048, 16, 32, 8, 128256, 6.0),
    "llama3-8b": (4096, 32, 32, 8, 128256, 5.25),
}
