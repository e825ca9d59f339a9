"""The encoder's operation count against a hand count at a tiny size.

d=4, 2 heads of head_dim 2 (so 2d=8, value_dim 4, head size 2), ff 4,
1 position, 1 layer pair, 1 feature:
mLSTM projections 2(4*8 + 4*8 + 8*4 + 8*4 + 4*4 + 8*4) = 352, memory
2 heads x (5*2*4 + 4*2) = 96; sLSTM 2(4*16 + 2*2*8 + 4*8 + 4*4) = 288;
final projection 2*4*1 = 8. Total 744.
"""
import flops


def test_hand_count():
    enc = dict(d_model=4, n_heads=2, head_dim=2, seq_len=1, ff_inner=4,
               n_features=1, n_layers=2)
    assert flops.encoder_flops_per_row(enc) == 744.0


def test_scales_with_positions_and_layers():
    enc = dict(d_model=768, n_heads=4, head_dim=192, seq_len=64,
               ff_inner=2048, n_features=8, n_layers=12)
    one = flops.encoder_flops_per_row(dict(enc, seq_len=1, n_features=0))
    assert flops.encoder_flops_per_row(dict(enc, n_features=0)) == 64 * one
    # about 2 operations per non-embedding weight per position
    assert 1.5e8 < one < 2.0e8
