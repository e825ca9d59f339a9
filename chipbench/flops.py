"""Operations of the text encoder per embedded row, from its shapes.

One row is one text, run over all ``seq_len`` positions (the encoder
computes every position; pooling then masks the padding). Per position
and per (mLSTM, sLSTM) layer pair, counting a multiply-add as 2:

- mLSTM projections: up and gate (d -> 2d each), queries and keys
  (2d -> heads x head_dim each), input/forget gates (d -> 2 heads), down
  (2d -> d);
- mLSTM memory, in its recurrent form: per head the update of the
  head_dim x value_dim matrix memory (a decay multiply and an outer
  product add: 3 per entry), its read-out (2 per entry), and the
  normalizer's update and read-out (4 per key entry);
- sLSTM projections: gates (d -> 4d), the per-head recurrent gates
  (head size -> 4 x head size), the gated feed-forward up (d -> 2 ff) and
  down (ff -> d).

Plus the final projection of the pooled row to ``n_features``. Norms,
gates' nonlinearities and the embedding gather are left out (they are
not matrix work), so the count is a floor of what the chip must do.
"""
from __future__ import annotations


def encoder_flops_per_row(enc: dict) -> float:
    d, H, dqk = enc["d_model"], enc["n_heads"], enc["head_dim"]
    T, ff, F = enc["seq_len"], enc["ff_inner"], enc["n_features"]
    di = 2 * d
    dv, dh = di // H, d // H
    mlstm = 2 * (d * di + d * di + di * H * dqk + di * H * dqk
                 + d * 2 * H + di * d) \
        + H * (5 * dqk * dv + 4 * dqk)
    slstm = 2 * (d * 4 * d + H * dh * 4 * dh + d * 2 * ff + ff * d)
    pairs = enc["n_layers"] // 2
    return float(T * pairs * (mlstm + slstm) + 2 * d * F)
