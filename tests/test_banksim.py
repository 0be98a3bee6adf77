"""Deterministic tests for the banked-endpoint simulator (core/banksim.py).

The conflict counts below are hand-derived from the crossbar's arbitration
rules (one grant per bank per cycle, words issued round-robin across
``n_ports`` lanes, one packed beat retired per cycle) on streams small
enough to trace by hand, then pinned exactly.  The page-walk replay
regression feeds page-table stream descriptors — one indirect stream per
sequence over its page-table row, and the prefix-sharing ``remap_only``
kind — through the simulator and pins their cycle counts.
"""
import math

import numpy as np
import pytest

from repro.core.banksim import (
    BankConfig,
    crossbar_area_kge,
    indirect_utilization,
    simulate_stream,
    simulate_words,
    strided_utilization,
)
from repro.core.streams import IndirectStream, StridedStream


# ---------------------------------------------------------------------------
# simulate_words: hand-computed conflict counts
# ---------------------------------------------------------------------------

def test_unit_stride_is_conflict_free():
    """8 consecutive words over 2 ports / 2 banks alternate banks perfectly:
    each cycle both lanes hit different banks, so every beat needs exactly
    one fetch cycle + pipelining — utilization 1.0, zero stalls."""
    cfg = BankConfig(n_ports=2, n_banks=2)
    r = simulate_words(np.arange(8, dtype=np.int64), cfg)
    assert r.data_beats == 4
    assert r.utilization == 1.0
    assert r.stall_cycles == 0


def test_stride_two_aliases_to_one_bank():
    """Words 0,2,4,6 all land in bank 0 (addr % 2 == 0): the two lanes
    serialize on the single bank, so each 2-word beat takes 2 fetch cycles —
    exactly half utilization."""
    cfg = BankConfig(n_ports=2, n_banks=2)
    r = simulate_words(np.array([0, 2, 4, 6], dtype=np.int64), cfg)
    assert r == type(r)(cycles=4, data_beats=2, utilization=0.5,
                        stall_cycles=2)


def test_ideal_flag_ignores_conflicts():
    cfg = BankConfig(n_ports=2, n_banks=2, ideal=True)
    r = simulate_words(np.array([0, 2, 4, 6], dtype=np.int64), cfg)
    assert r.cycles == 2 and r.utilization == 1.0 and r.stall_cycles == 0


def test_beats_round_up_to_port_width():
    """5 words over 4 ports = 2 beats (the last beat is partial)."""
    cfg = BankConfig(n_ports=4, n_banks=5)
    r = simulate_words(np.arange(5, dtype=np.int64), cfg)
    assert r.data_beats == math.ceil(5 / 4) == 2


# ---------------------------------------------------------------------------
# simulate_stream: descriptor-level behaviour (§III-E shapes)
# ---------------------------------------------------------------------------

def test_strided_prime_banks_beat_power_of_two():
    """stride-4 words alias mod 16 but sweep all residues mod 17: the prime
    endpoint is conflict-free while the pow2 one halves its throughput."""
    s = StridedStream(base=0, elem_bits=32, count=64, stride=4)
    r17 = simulate_stream(s, BankConfig(n_ports=8, n_banks=17))
    r16 = simulate_stream(s, BankConfig(n_ports=8, n_banks=16))
    assert r17.utilization == 1.0 and r17.stall_cycles == 0
    assert r16 == type(r16)(cycles=16, data_beats=8, utilization=0.5,
                            stall_cycles=8)


def test_strided_utilization_sensitivity():
    """Fig. 5b ordering on the worst-case power-of-two stride."""
    u16 = strided_utilization(8, BankConfig(n_ports=8, n_banks=16))
    u17 = strided_utilization(8, BankConfig(n_ports=8, n_banks=17))
    assert u16 == 0.25
    assert u17 == 1.0


def test_indirect_index_stage_shares_ports():
    """16 one-word elements = 2 data beats, but the 32-bit index line for
    each 8-element group must drain through the same ports first: the
    index/element round-robin costs the r/(r+1) ceiling of §III-B — here
    the measured schedule is 4 cycles for 2 beats."""
    idx = np.arange(16)[::-1].copy()
    s = IndirectStream(base=0, elem_bits=32, count=16, indices=idx,
                       index_bits=32)
    r = simulate_stream(s, BankConfig(n_ports=8, n_banks=17))
    assert r == type(r)(cycles=4, data_beats=2, utilization=0.5,
                        stall_cycles=2)


def test_indirect_utilization_below_index_ceiling():
    """Random 32-bit-index / 32-bit-element bursts can never beat r/(r+1)
    with r = 1 (one element word per index word): utilization ≤ 1/2, and a
    prime bank count shows no inherent advantage (§III-E)."""
    for banks in (16, 17):
        u = indirect_utilization(BankConfig(n_ports=8, n_banks=banks))
        assert 0.0 < u <= 0.5 + 1e-9


# ---------------------------------------------------------------------------
# Page walks: paged-KV descriptors through the endpoint
# ---------------------------------------------------------------------------

#: One KV page as a stream element: 4 tokens of 64 bytes, in bits.
PAGE_BITS = 4 * 64 * 8


def _page_walk(row, length, page=4, remap_only=False):
    """The indirect stream over the pages holding ``length`` tokens of a
    page-table ``row``: the table row is the memory-resident index vector."""
    n = -(-length // page)
    return IndirectStream(base=0, elem_bits=PAGE_BITS, count=n,
                          indices=np.asarray(row[:n], dtype=np.int64),
                          index_bits=32, remap_only=remap_only)


def test_page_table_streams_replay_pinned():
    """The paged-KV gather descriptors (one indirect stream per active
    sequence, elem_bits = one page's bytes) replay through the simulator
    with pinned cycle counts: a 3-page walk costs 25 cycles (24 data beats
    + 1 index stall) at the 8×17 endpoint."""
    table = np.array([[3, 1, 2, 0], [5, 4, 0, 0]])
    streams = [_page_walk(row, n) for row, n in zip(table, (10, 6))]
    np.testing.assert_array_equal(streams[0].indices, [3, 1, 2])
    np.testing.assert_array_equal(streams[1].indices, [5, 4])
    cfg = BankConfig(n_ports=8, n_banks=17)
    r0, r1 = (simulate_stream(s, cfg) for s in streams)
    assert (r0.cycles, r0.data_beats, r0.stall_cycles) == (25, 24, 1)
    assert (r1.cycles, r1.data_beats, r1.stall_cycles) == (17, 16, 1)
    assert r0.utilization == pytest.approx(24 / 25)


def test_share_table_streams_remap_only_replay():
    """Prefix-sharing remap descriptors move no KV payload: only the table
    entries (one 32-bit index per shared page) cross the endpoint, so a
    3-page share drains in a single cycle."""
    s = _page_walk([3, 1, 2], 12, remap_only=True)
    assert s.remap_only and s.count == 3
    r = simulate_stream(s, BankConfig(n_ports=8, n_banks=17))
    assert r == type(r)(cycles=1, data_beats=1, utilization=1.0,
                        stall_cycles=0)
    # The equivalent *copy* would have drained the full page payload:
    full = simulate_stream(_page_walk([3, 1, 2, 0], 12),
                           BankConfig(n_ports=8, n_banks=17))
    assert full.data_beats > r.data_beats * 8  # >8× fewer beats via remap


# ---------------------------------------------------------------------------
# Area model sanity
# ---------------------------------------------------------------------------

def test_crossbar_area_prime_overhead_shrinks():
    """Prime bank counts pay a fixed mod/div cost per port, so the relative
    overhead over the neighbouring pow2 design shrinks with bank count."""
    over_16 = crossbar_area_kge(8, 17) / crossbar_area_kge(8, 16)
    over_32 = crossbar_area_kge(8, 37) / crossbar_area_kge(8, 32)
    assert over_16 > over_32 > 1.0
    assert crossbar_area_kge(8, 16) == pytest.approx(55.0)
