"""Operation and byte counts against hand counts."""
from bench import counts


def test_encdec_flops_hand_count():
    # d=2, d_ff=4, F=3 frames, S=2 tokens, V=5, one layer each side, batch 1
    d, f, F, S, V = 2, 4, 3, 2, 5
    enc = F * (8 * d * d + 4 * d * f + 4 * F * d)        # 3 * (32+32+24)
    dec = S * (8 * d * d + 4 * d * f + 4 * d * d + 4 * F * d) \
        + 4 * d * (S * (S + 1) // 2) + 4 * d * d * F       # causal pairs: 3
    head = 2 * d * V * S
    assert enc == 264 and dec == 2 * 104 + 24 + 48 and head == 40
    got = counts.encdec_train_flops(batch=1, seq=S, frames=F, d_model=d,
                                    d_ff=f, vocab=V, enc_layers=1,
                                    dec_layers=1)
    assert got == 3 * (264 + 280 + 40)
    assert counts.encdec_train_flops(batch=3, seq=S, frames=F, d_model=d,
                                     d_ff=f, vocab=V, enc_layers=1,
                                     dec_layers=1) == 3 * got


def test_whisper_base_step_flops():
    # batch 4 x 448 tokens, 1500 frames: ~1.76 TFLOP a step
    got = counts.encdec_train_flops(batch=4, seq=448, frames=1500,
                                    d_model=512, d_ff=2048, vocab=51865,
                                    enc_layers=6, dec_layers=6)
    assert 1.70e12 < got < 1.80e12


def test_arena_kernel_costs_and_roofline():
    flops, nbytes = counts.gram_row_cost(14, 1000, 4)
    assert (flops, nbytes) == (28000.0, 56000.0)
    peak = {"flops_bf16": 1e3, "hbm_bytes_per_s": 1e6}
    assert counts.least_time(28000.0, 56000.0, peak) == 28.0   # compute
    peak["flops_bf16"] = 1e9
    assert counts.least_time(28000.0, 56000.0, peak) == 0.056  # memory
