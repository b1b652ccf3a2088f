"""Simulator / workload / metrics / cluster tests incl. hypothesis
conservation properties."""
import copy

import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.serving.cluster import FragmentedCluster
from repro.serving.metrics import ServingStats
from repro.serving.simulator import ClusterSim, POLICIES, table2_profile
from repro.serving.workload import Phase, phased_trace, synth_requests


class TestWorkload:
    @settings(max_examples=8, deadline=None)
    @given(cv=st.sampled_from([0.5, 1.0, 3.0]), rate=st.sampled_from([10.0, 50.0]))
    def test_rate_and_cv(self, cv, rate):
        rng = np.random.default_rng(0)
        reqs = synth_requests(rng, rate=rate, cv=cv, duration=120.0)
        got_rate = len(reqs) / 120.0
        assert abs(got_rate - rate) / rate < 0.25
        ivs = np.diff([r.arrival for r in reqs])
        got_cv = ivs.std() / ivs.mean()
        assert abs(got_cv - cv) / cv < 0.3

    def test_phases_are_ordered(self):
        rng = np.random.default_rng(1)
        reqs = phased_trace(rng, [Phase(10, 5, 1.0), Phase(10, 50, 4.0)])
        ts = [r.arrival for r in reqs]
        assert ts == sorted(ts)

    def test_deterministic_under_fixed_seed(self):
        def gen():
            return synth_requests(np.random.default_rng(7), rate=20.0,
                                  cv=2.0, duration=30.0,
                                  priority_mix=(0.2, 0.6, 0.2))
        a, b = gen(), gen()
        assert len(a) == len(b)
        assert all((x.rid, x.arrival, x.prompt_len, x.max_new_tokens,
                    x.priority) ==
                   (y.rid, y.arrival, y.prompt_len, y.max_new_tokens,
                    y.priority) for x, y in zip(a, b))

    def test_priority_mix_none_preserves_legacy_stream(self):
        # priority_mix=None must not consume extra rng draws — older
        # seeds/benchmarks depend on the exact arrival/length stream
        a = synth_requests(np.random.default_rng(3), rate=20.0, cv=1.0,
                           duration=20.0)
        b = synth_requests(np.random.default_rng(3), rate=20.0, cv=1.0,
                           duration=20.0, priority_mix=None)
        assert [r.arrival for r in a] == [r.arrival for r in b]
        assert all(r.priority == 1 for r in a)

    def test_priority_mix_draws_all_classes(self):
        reqs = synth_requests(np.random.default_rng(5), rate=50.0, cv=1.0,
                              duration=30.0, priority_mix=(0.3, 0.4, 0.3))
        prios = {r.priority for r in reqs}
        assert prios == {0, 1, 2}

    def test_duration_bound_and_length_clamps(self):
        t0 = 100.0
        reqs = synth_requests(np.random.default_rng(11), rate=40.0, cv=3.0,
                              duration=25.0, t0=t0, prompt_mean=16,
                              decode_mean=4)
        assert reqs, "trace must not be empty"
        assert all(t0 < r.arrival <= t0 + 25.0 for r in reqs)
        assert all(16 <= r.prompt_len <= 8192 for r in reqs)
        assert all(4 <= r.max_new_tokens <= 1024 for r in reqs)

    def test_phased_trace_unique_monotone_rids(self):
        rng = np.random.default_rng(2)
        reqs = phased_trace(rng, [Phase(15, 10, 0.5), Phase(15, 40, 3.0),
                                  Phase(15, 10, 1.0)])
        rids = [r.rid for r in reqs]
        assert rids == list(range(len(reqs)))    # unique + contiguous
        assert [r.arrival for r in reqs] == sorted(r.arrival for r in reqs)
        # each phase's arrivals stay inside its window
        assert max(r.arrival for r in reqs) <= 45.0


class TestCluster:
    def test_fragmentation_stats_match_paper(self):
        cl = FragmentedCluster.synth(np.random.default_rng(0),
                                     n_servers=430, n_gpus=468)
        assert 0.03 < cl.p_free_gpu() < 0.2           # paper: 0.087
        assert cl.p_colocated(4) < 0.02               # paper: 0.0002
        assert 1.5 < cl.subscription_rate() < 2.5     # paper: 2.16

    def test_allocate_release(self):
        cl = FragmentedCluster.synth(np.random.default_rng(0))
        gpus = cl.find_gpus(4, 5e9)
        assert gpus
        free_before = [g.free_mem for g in gpus]
        cl.allocate(gpus, 5e9)
        assert all(g.free_mem == f - 5e9 for g, f in zip(gpus, free_before))


class TestSimulator:
    def _run(self, name, cv, seed=0, duration=240.0):
        rng = np.random.default_rng(seed)
        reqs = synth_requests(rng, rate=20.0, cv=cv, duration=duration,
                              deadline_s=4.0)
        sim = ClusterSim(POLICIES[name],
                         FragmentedCluster.synth(np.random.default_rng(1)),
                         np.random.default_rng(2), slo=4.0)
        return sim.run(copy.deepcopy(reqs)), len(reqs)

    def test_no_request_lost(self):
        out, n = self._run("flexpipe", cv=2.0)
        assert out["completed"] == n

    def test_goodput_bounded_by_offered_load(self):
        out, n = self._run("alpaserve", cv=1.0)
        assert out["goodput"] <= n / 240.0 * 1.05

    def test_flexpipe_beats_static_under_burst(self):
        fp, _ = self._run("flexpipe", cv=6.0, duration=300.0)
        ap, _ = self._run("alpaserve", cv=6.0, duration=300.0)
        assert fp["latency"]["p99"] < ap["latency"]["p99"]
        assert fp["refactor_count"] > 0

    def test_table2_profile_trends(self):
        p4, p32 = table2_profile(4), table2_profile(32)
        assert p32.load_time < p4.load_time          # 8.7x faster load
        assert p32.comm_ms > p4.comm_ms              # more hops
        assert p32.batch > p4.batch                  # bigger batches


class TestMetrics:
    def test_stall_detection(self):
        s = ServingStats()
        for i in range(100):                          # baseline ~1.0
            s.record(70.0 + i * 0.1, 1.0, True)
        for i in range(20):                           # stall at ~5x
            s.record(82.0 + i * 0.2, 5.0, False)
        for i in range(50):
            s.record(90.0 + i * 0.2, 1.0, True)
        eps = s.stall_episodes(window=1.0, start_after=0.0)
        assert len(eps) >= 1
        assert eps[0]["peak"] >= 5.0

    def test_goodput_counts_only_slo_met(self):
        s = ServingStats()
        s.record(1.0, 0.5, True)
        s.record(2.0, 9.0, False)
        assert s.goodput(10.0) == pytest.approx(0.1)

    def test_block_tally_summarises_every_tick(self):
        """The fixed-memory tally reads what the per-tick samples it
        replaced read: means over ticks and the extremes."""
        rng = np.random.default_rng(0)
        used = rng.integers(0, 100, 50)
        free = 100 - used
        frag = rng.random(50)
        s = ServingStats()
        assert s.block_summary() == {"mean_used": 0.0, "max_used": 0,
                                     "min_free": 0, "mean_frag": 0.0,
                                     "max_frag": 0.0}
        for u, f, g in zip(used, free, frag):
            s.record_blocks(int(u), int(f), float(g))
        got = s.block_summary()
        assert got == pytest.approx({
            "mean_used": used.mean(), "max_used": used.max(),
            "min_free": free.min(), "mean_frag": frag.mean(),
            "max_frag": frag.max()})
        assert s.overload_summary()["blocks"] == got
