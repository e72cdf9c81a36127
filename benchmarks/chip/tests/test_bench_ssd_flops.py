"""The SSD scan's operation and byte counts (bench/ssd_flops.py), against
a count made element by element at a small shape and the published
widths' totals."""
import pytest

from bench import ssd_flops


def _hand_count(B, L, H, P, G, N, cl):
    """Multiply-adds of the chunked scan, pair by pair: C_i·B_j for j <= i
    per group, M_ij·x_j for j <= i per head, and per head and step the
    state read by C and the step's update of the state."""
    macs = 0
    for _ in range(B):
        for _ in range(L // cl):
            pairs = sum(1 for i in range(cl) for j in range(cl) if j <= i)
            macs += G * pairs * N
            macs += H * (pairs * P + cl * P * N + cl * P * N)
    return 2 * macs


@pytest.mark.parametrize("B,L,H,P,G,N,cl", [(1, 4, 2, 1, 1, 1, 2),
                                            (2, 24, 6, 3, 2, 5, 8)])
def test_ssd_flops_match_a_hand_count(B, L, H, P, G, N, cl):
    f, b = ssd_flops.ssd(B, L, H, P, G, N, cl, itemsize=2)
    assert f == _hand_count(B, L, H, P, G, N, cl)
    x_y = 2 * 2 * B * H * L * P            # x read, y written, bf16
    bc = 2 * 2 * B * G * L * N             # B and C read once
    f32 = 4 * (B * H * L + 2 * H + B * H * P * N)  # dt, A, D, state
    assert b == x_y + bc + f32


def test_ssd_counts_at_the_published_widths():
    """Nemotron-H-47B's mixer over 8192 tokens: 156.9 GFLOP and 629 MB a
    call, 0.80 ms and 0.77 ms at a v5e's peaks."""
    f, b = ssd_flops.ssd(1, 8192, 256, 64, 8, 256, 128, itemsize=2)
    assert f / 1e9 == pytest.approx(156.9, abs=0.05)
    assert b / 1e6 == pytest.approx(629.1, abs=0.05)
