"""Spartus's DeltaLSTM (C. Gao, T. Delbruck, S.-C. Liu, IEEE TNNLS 2022,
arXiv:2108.02297) at the size of its TIMIT network: a 2-layer ΔLSTM of
1024 units per layer, served on the DeltaKWS IC's datapath — the
10-channel IIR FEx in front and an FC-12 GSCD head on the top layer."""
import dataclasses
from repro.configs.base import ArchConfig

CONFIG = ArchConfig(
    name="spartus", family="kws",
    num_layers=2, d_model=1024, n_heads=0, n_kv_heads=0, d_head=0,
    d_ff=0, vocab_size=12,
    use_delta=True, delta_threshold=0.2, cell="lstm",
    frontend="iir_fex", frontend_tokens=10,
)

SMOKE_CONFIG = dataclasses.replace(CONFIG, d_model=32)
