"""Distributed-layer tests: broker semantics + fault injection.

SURVEY.md §4 "Consequence for the rebuild": distributed tests without a
cluster — in-process broker, worker threads/processes, fault injection
(worker death mid-job ⇒ redelivery), all on localhost TCP.
"""

import multiprocessing
import os
import signal
import socket
import threading
import time

import numpy as np
import pytest

from gentun_tpu import GeneticAlgorithm, Individual, Population, genetic_cnn_genome
from gentun_tpu.distributed import (
    AuthError,
    DistributedGridPopulation,
    DistributedPopulation,
    GentunClient,
    JobBroker,
    JobFailed,
)
from gentun_tpu.distributed.protocol import decode, encode


class OneMax(Individual):
    """Cheap deterministic fitness: count of set bits."""

    def build_spec(self, **params):
        return genetic_cnn_genome(tuple(params.get("nodes", (4, 4))))

    def evaluate(self):
        return float(sum(sum(g) for g in self.genes.values()))


class SlowOneMax(OneMax):
    def evaluate(self):
        time.sleep(float(self.additional_parameters.get("delay", 0.5)))
        return super().evaluate()


class AlwaysFails(OneMax):
    def evaluate(self):
        raise RuntimeError("boom")


class FlakyOneMax(OneMax):
    """Fails on the all-zero genome for its first two attempts, then heals
    (worker threads share this process's memory, so the counter is visible)."""

    attempts = 0

    def evaluate(self):
        if sum(sum(g) for g in self.genes.values()) == 0:
            FlakyOneMax.attempts += 1
            if FlakyOneMax.attempts <= 2:
                raise RuntimeError("flaky boom")
        return super().evaluate()


DATA = (np.zeros(1, np.float32), np.zeros(1, np.float32))


def _run_worker(species, port, password=None, capacity=1, max_jobs=None, delay_params=None):
    client = GentunClient(
        species,
        *DATA,
        host="127.0.0.1",
        port=port,
        password=password,
        capacity=capacity,
        heartbeat_interval=0.2,
        reconnect_delay=0.1,
    )
    return client.work(max_jobs=max_jobs)


def _start_worker_thread(species, port, **kw):
    stop = threading.Event()
    t = threading.Thread(
        target=lambda: GentunClient(
            species, *DATA, host="127.0.0.1", port=port,
            password=kw.get("password"), capacity=kw.get("capacity", 1),
            heartbeat_interval=0.2, reconnect_delay=0.1,
            fitness_store=kw.get("fitness_store"),
        ).work(stop_event=stop),
        daemon=True,
    )
    t.start()
    return stop, t


def _worker_process_main(port):
    """Forked worker that takes a slow job — the kill-target."""
    _run_worker(SlowOneMax, port)


@pytest.fixture
def pop4():
    p = DistributedPopulation(OneMax, size=4, seed=0, port=0)
    yield p
    p.close()


class TestBrokerBasics:
    def test_evaluate_with_one_worker(self, pop4):
        _, port = pop4.broker_address
        stop, _ = _start_worker_thread(OneMax, port)
        try:
            pop4.evaluate()
            fits = [ind.get_fitness() for ind in pop4]
            expected = [float(sum(sum(g) for g in ind.genes.values())) for ind in pop4]
            assert fits == expected
        finally:
            stop.set()

    def test_competing_consumers_split_work(self):
        with DistributedPopulation(OneMax, size=12, seed=1, port=0) as pop:
            _, port = pop.broker_address
            stops = [_start_worker_thread(OneMax, port)[0] for _ in range(3)]
            try:
                pop.evaluate()
                assert all(ind.fitness_evaluated for ind in pop)
            finally:
                for s in stops:
                    s.set()

    def test_capacity_batching(self):
        """capacity>1 workers receive job batches and answer them all."""
        with DistributedPopulation(OneMax, size=8, seed=2, port=0) as pop:
            _, port = pop.broker_address
            stop, _ = _start_worker_thread(OneMax, port, capacity=4)
            try:
                pop.evaluate()
                assert all(ind.fitness_evaluated for ind in pop)
            finally:
                stop.set()

    def test_capacity_batch_arrives_as_one_frame(self):
        """Credit-based prefetch: a capacity-8 worker's whole batch arrives in
        a single `jobs` frame — no drain window, latency-independent."""
        broker = JobBroker(port=0).start()
        try:
            _, port = broker.address
            payloads = {
                f"j{i}": {"genes": {"S_1": [i]}, "additional_parameters": {}}
                for i in range(8)
            }
            broker.submit(payloads)
            sock = socket.create_connection(("127.0.0.1", port))
            rfile = sock.makefile("rb")
            sock.sendall(encode({"type": "hello", "worker_id": "probe", "capacity": 8}))
            assert decode(rfile.readline())["type"] == "welcome"
            sock.sendall(encode({"type": "ready", "credit": 8}))
            msg = decode(rfile.readline())
            assert msg["type"] == "jobs"
            assert len(msg["jobs"]) == 8  # ALL co-delivered jobs, one frame
            sock.close()
        finally:
            broker.stop()

    def test_bad_token_rejected(self):
        with DistributedPopulation(OneMax, size=2, seed=0, port=0, password="s3cret") as pop:
            _, port = pop.broker_address
            # wrong password: worker is rejected, jobs stay pending
            client = GentunClient(OneMax, *DATA, port=port, password="wrong", reconnect_delay=0.05)
            with pytest.raises((ConnectionError, OSError)):
                client._connect()
            # right password: work completes
            stop, _ = _start_worker_thread(OneMax, port, password="s3cret")
            try:
                pop.evaluate()
                assert all(ind.fitness_evaluated for ind in pop)
            finally:
                stop.set()

    def test_auth_failure_is_terminal(self):
        """A wrong token must make work() raise promptly, not spin in the
        reconnect loop forever (VERDICT r2 weak #2)."""
        with DistributedPopulation(OneMax, size=2, seed=0, port=0, password="s3cret") as pop:
            _, port = pop.broker_address
            client = GentunClient(OneMax, *DATA, port=port, password="wrong", reconnect_delay=0.05)
            t0 = time.monotonic()
            with pytest.raises(AuthError):
                client.work()
            assert time.monotonic() - t0 < 5.0  # terminal, not a retry loop

    def test_gather_timeout(self):
        with DistributedPopulation(OneMax, size=2, seed=0, port=0, job_timeout=0.3) as pop:
            with pytest.raises(TimeoutError):
                pop.evaluate()  # no workers connected
            # timeout prunes + cancels: no state left to leak, and a retry
            # starts clean (late results would be dropped as stale)
            time.sleep(0.2)  # let the loop thread process the cancel
            assert pop.broker._results == {}
            assert pop.broker._failures == {}
            assert pop.broker._payloads == {}
            assert pop.broker._sched.depth() == 0  # cancelled ids drained too

    def test_non_ascii_password_accepted(self):
        """hmac token compare must handle non-ASCII secrets (UTF-8 bytes)."""
        with DistributedPopulation(
            OneMax, size=2, seed=0, port=0, password="sécret", job_timeout=10.0,
        ) as pop:
            _, port = pop.broker_address
            stop, _ = _start_worker_thread(OneMax, port, password="sécret")
            try:
                pop.evaluate()
                assert all(ind.fitness_evaluated for ind in pop)
            finally:
                stop.set()

    def test_fail_fast_when_failure_and_no_workers(self):
        """A recorded permanent failure + zero connected workers must not
        hang a timeout-less gather: the barrier fails fast and cancels."""
        with DistributedPopulation(
            AlwaysFails, size=3, seed=5, port=0, max_attempts=1, job_timeout=None,
            heartbeat_timeout=1.0,  # fail-fast waits a full heartbeat window
        ) as pop:
            _, port = pop.broker_address

            def fail_one_then_vanish():
                sock = socket.create_connection(("127.0.0.1", port))
                rfile = sock.makefile("rb")
                sock.sendall(encode({"type": "hello", "worker_id": "quitter", "capacity": 1}))
                assert decode(rfile.readline())["type"] == "welcome"
                sock.sendall(encode({"type": "ready", "credit": 1}))
                msg = decode(rfile.readline())
                job_id = msg["jobs"][0]["job_id"]
                sock.sendall(encode({"type": "fail", "job_id": job_id, "reason": "boom"}))
                time.sleep(0.2)  # let the broker record the failure
                sock.close()  # vanish with 2 jobs still pending, no workers left

            t = threading.Thread(target=fail_one_then_vanish, daemon=True)
            t.start()
            done = {}

            def master():
                try:
                    pop.evaluate()
                except JobFailed as e:
                    done["failures"] = len(e.failures)

            mt = threading.Thread(target=master, daemon=True)
            mt.start()
            mt.join(timeout=20.0)
            assert not mt.is_alive(), "gather hung despite permanent failure + no workers"
            assert done.get("failures", 0) >= 1

    def test_duplicate_result_first_wins(self):
        broker = JobBroker(port=0).start()
        try:
            broker.submit({"j1": {"genes": {}, "additional_parameters": {}}})
            time.sleep(0.2)  # let the loop thread enqueue

            class W:  # stand-in worker for the dedup bookkeeping
                def __init__(self):
                    self.in_flight = {"j1"}

            broker._on_result(W(), {"type": "result", "job_id": "j1", "fitness": 1.0})
            # redelivery race: a second worker reports later — dropped
            broker._on_result(W(), {"type": "result", "job_id": "j1", "fitness": 9.0})
            assert broker.gather(["j1"], timeout=1.0) == {"j1": 1.0}
            # gather pruned master-side state (SURVEY.md long-search hygiene)
            assert broker._results == {} and broker._payloads == {}
        finally:
            broker.stop()


class TestFaultInjection:
    def test_worker_killed_mid_job_redelivers(self):
        """SIGKILL a worker holding a job; the survivor finishes everything."""
        with DistributedPopulation(
            SlowOneMax, size=3, seed=3, port=0,
            additional_parameters={"delay": 0.6},
        ) as pop:
            _, port = pop.broker_address
            ctx = multiprocessing.get_context("fork")
            victim = ctx.Process(target=_worker_process_main, args=(port,), daemon=True)
            victim.start()

            done = {}

            def master():
                pop.evaluate()
                done["ok"] = all(ind.fitness_evaluated for ind in pop)

            mt = threading.Thread(target=master, daemon=True)
            mt.start()
            time.sleep(1.0)  # victim has taken a job and is mid-evaluation
            os.kill(victim.pid, signal.SIGKILL)
            victim.join(timeout=5.0)

            stop, _ = _start_worker_thread(SlowOneMax, port)
            try:
                mt.join(timeout=30.0)
                assert done.get("ok"), "master barrier did not complete after redelivery"
            finally:
                stop.set()

    def test_hung_worker_heartbeat_reaper_redelivers(self):
        """A worker that takes a job and goes silent (no pings) is reaped."""
        with DistributedPopulation(
            OneMax, size=2, seed=4, port=0, heartbeat_timeout=1.0,
        ) as pop:
            _, port = pop.broker_address
            # Hand-rolled zombie: speaks hello/ready, takes jobs, never pings.
            sock = socket.create_connection(("127.0.0.1", port))
            rfile = sock.makefile("rb")
            sock.sendall(encode({"type": "hello", "worker_id": "zombie", "capacity": 2}))
            assert decode(rfile.readline())["type"] == "welcome"
            sock.sendall(encode({"type": "ready", "credit": 2}))

            done = {}

            def master():
                pop.evaluate()
                done["ok"] = all(ind.fitness_evaluated for ind in pop)

            mt = threading.Thread(target=master, daemon=True)
            mt.start()
            # zombie receives the jobs, holds them silently
            time.sleep(0.5)
            stop, _ = _start_worker_thread(OneMax, port)
            try:
                mt.join(timeout=15.0)
                assert done.get("ok"), "reaper did not requeue the zombie's jobs"
            finally:
                stop.set()
                sock.close()

    def test_failing_job_exhausts_attempts(self):
        with DistributedPopulation(
            AlwaysFails, size=1, seed=5, port=0, max_attempts=2, job_timeout=20.0,
        ) as pop:
            _, port = pop.broker_address
            stop, _ = _start_worker_thread(AlwaysFails, port)
            try:
                with pytest.raises(JobFailed):
                    pop.evaluate()
                # gather pruned ALL failure state on raise (no leak across
                # generations, and a resubmit starts with fresh attempts)
                assert pop.broker._failures == {}
                assert pop.broker._fail_counts == {}
            finally:
                stop.set()

    def test_job_failed_keeps_partial_results_and_retry_reships_only_failures(self):
        """Post-JobFailed semantics: finished work is applied, evaluate()
        again reships only the failed individuals (with fresh attempts)."""
        FlakyOneMax.attempts = 0
        bad = {"S_1": (0,) * 6, "S_2": (0,) * 6}  # the genome FlakyOneMax chokes on
        good1 = {"S_1": (1,) * 6, "S_2": (1,) * 6}
        good2 = {"S_1": (1, 0, 1, 0, 1, 0), "S_2": (0, 1, 0, 1, 0, 1)}
        inds = [
            FlakyOneMax(genes=g, additional_parameters={"nodes": (4, 4)})
            for g in (good1, bad, good2)
        ]
        with DistributedPopulation(
            FlakyOneMax,
            individual_list=inds,
            additional_parameters={"nodes": (4, 4)},
            port=0,
            max_attempts=2,
            job_timeout=30.0,
        ) as pop:
            _, port = pop.broker_address
            stop, _ = _start_worker_thread(FlakyOneMax, port)
            try:
                with pytest.raises(JobFailed) as ei:
                    pop.evaluate()
                # the two healthy individuals kept their results
                assert pop[0].fitness_evaluated and pop[2].fitness_evaluated
                assert not pop[1].fitness_evaluated
                assert len(ei.value.failures) == 1
                assert len(ei.value.partial) == 2
                # retry: only the failed individual is reshipped; FlakyOneMax
                # has burnt its 2 failures and now succeeds
                shipped = pop.evaluate()
                assert shipped == 1
                assert pop[1].get_fitness() == 0.0
            finally:
                stop.set()


class GlitchyOneMax(OneMax):
    """Every distinct genome transiently fails its first two in-process
    evaluation attempts, then heals.  With broker ``max_attempts=2`` that
    deterministically exhausts delivery attempts for fresh work — a real
    mid-search ``JobFailed`` — while the next evaluate() pass (attempt 3)
    succeeds.  (Worker threads share this process's memory; a forked
    SlowOneMax process worker has its own state and just succeeds.)"""

    attempts: dict = {}

    def evaluate(self):
        key = tuple(sorted((k, tuple(v)) for k, v in self.genes.items()))
        n = GlitchyOneMax.attempts.get(key, 0)
        GlitchyOneMax.attempts[key] = n + 1
        if n < 2:
            raise RuntimeError(f"transient glitch (attempt {n + 1})")
        return super().evaluate()


class PoisonOneMax(OneMax):
    """Permanently fails the all-zero genome (never heals)."""

    def evaluate(self):
        if sum(sum(g) for g in self.genes.values()) == 0:
            raise RuntimeError("poison genome")
        return super().evaluate()


class TestSearchFailureRecovery:
    """VERDICT r2 'do this' #3: a long search survives transient failures."""

    def test_six_generation_search_survives_glitches_and_sigkill(self):
        """A 6-generation distributed search completes despite (a) a worker
        whose evaluations fail transiently — exhausting broker attempts and
        raising JobFailed mid-search — and (b) a worker SIGKILLed mid-job;
        the GA history records the retry passes."""
        GlitchyOneMax.attempts = {}
        with DistributedPopulation(
            GlitchyOneMax, size=6, seed=11, port=0,
            additional_parameters={"nodes": (4, 4), "delay": 0.5},
            max_attempts=2, job_timeout=60.0, evaluate_retries=3,
        ) as pop:
            _, port = pop.broker_address
            ctx = multiprocessing.get_context("fork")
            victim = ctx.Process(target=_worker_process_main, args=(port,), daemon=True)
            victim.start()
            stop, _ = _start_worker_thread(GlitchyOneMax, port)
            result = {}

            def search():
                ga = GeneticAlgorithm(pop, seed=11)
                result["best"] = ga.run(6)
                result["history"] = ga.history

            st = threading.Thread(target=search, daemon=True)
            st.start()
            time.sleep(1.0)  # mid-search: victim is (or was) holding a job
            os.kill(victim.pid, signal.SIGKILL)
            victim.join(timeout=5.0)
            try:
                st.join(timeout=90.0)
                assert not st.is_alive(), "search did not survive the failures"
                assert result["best"].get_fitness() >= 8
                assert len(result["history"]) == 6
                retried = [h for h in result["history"] if h.get("evaluate_retries")]
                assert retried, "no generation recorded a retry pass"
                assert all(not h.get("penalized") for h in result["history"])
            finally:
                stop.set()

    def test_penalize_policy_keeps_search_alive_on_permanent_failure(self):
        """failed_policy='penalize': a permanently-failing individual gets
        the generation's worst fitness (uncached) instead of killing the
        search; eval_stats records it."""
        bad = {"S_1": (0,) * 6, "S_2": (0,) * 6}
        good = {"S_1": (1,) * 6, "S_2": (0, 1) * 3}
        inds = [
            PoisonOneMax(genes=g, additional_parameters={"nodes": (4, 4)})
            for g in (good, bad)
        ]
        with DistributedPopulation(
            PoisonOneMax, individual_list=inds,
            additional_parameters={"nodes": (4, 4)},
            port=0, max_attempts=1, job_timeout=30.0,
            evaluate_retries=1, failed_policy="penalize",
        ) as pop:
            _, port = pop.broker_address
            stop, _ = _start_worker_thread(PoisonOneMax, port)
            try:
                completed = pop.evaluate()
                assert completed == 1  # only the healthy individual trained
                assert pop.eval_stats["penalized"] == 1
                assert pop.eval_stats["retries"] == 1
                good_fit = pop[0].get_fitness()
                assert pop[1].get_fitness() == good_fit  # worst observed = only observed
                # the penalty must NOT pollute the fitness cache
                key = pop._safe_cache_key(pop[1])
                assert key not in pop.fitness_cache
            finally:
                stop.set()

    def test_unknown_failed_policy_rejected(self):
        with pytest.raises(ValueError):
            DistributedPopulation(OneMax, size=2, port=0, failed_policy="shrug")


class TestDistributedGA:
    def test_full_search_over_workers(self):
        """BASELINE config #4's shape on one host: GA × broker × 2 workers."""
        with DistributedPopulation(OneMax, size=8, seed=6, port=0) as pop:
            _, port = pop.broker_address
            stops = [_start_worker_thread(OneMax, port)[0] for _ in range(2)]
            try:
                ga = GeneticAlgorithm(pop, seed=6)
                best = ga.run(3)
                assert best.get_fitness() >= 9  # (4,4) nodes → 12 bits max
                # clone_with preserved distribution across generations
                assert isinstance(ga.population, DistributedPopulation)
                assert ga.population.broker is pop.broker
            finally:
                for s in stops:
                    s.set()

    def test_grid_population_distributed(self):
        with DistributedGridPopulation(
            OneMax,
            genes_grid={"S_1": [(0,) * 6, (1,) * 6], "S_2": [(1,) * 6]},
            additional_parameters={"nodes": (4, 4)},
            port=0,
        ) as pop:
            assert len(pop) == 2
            _, port = pop.broker_address
            stop, _ = _start_worker_thread(OneMax, port)
            try:
                fits = pop.get_fitnesses()
                assert sorted(fits) == [6.0, 12.0]
            finally:
                stop.set()


def test_clone_with_preserves_type_for_plain_population():
    pop = Population(OneMax, *DATA, size=3, seed=0)
    clone = pop.clone_with(list(pop.individuals))
    assert type(clone) is Population
    assert clone.rng is pop.rng


class CountingOneMax(OneMax):
    """Worker-side eval counter (worker threads share this process's memory)."""

    evals = 0

    def evaluate(self):
        CountingOneMax.evals += 1
        return super().evaluate()


class TestMasterSideDedup:
    def test_duplicate_genomes_ship_one_job(self):
        CountingOneMax.evals = 0
        dup = {"S_1": (1, 0, 1, 0, 1, 0), "S_2": (1, 1, 0, 0, 1, 0)}
        other = {"S_1": (0,) * 6, "S_2": (1,) * 6}
        inds = [
            CountingOneMax(genes=g, additional_parameters={"nodes": (4, 4)})
            for g in (dup, dup, dup, other)
        ]
        with DistributedPopulation(
            CountingOneMax,
            individual_list=inds,
            additional_parameters={"nodes": (4, 4)},
            port=0,
        ) as pop:
            _, port = pop.broker_address
            stop, _ = _start_worker_thread(CountingOneMax, port)
            try:
                pop.evaluate()
            finally:
                stop.set()
        assert CountingOneMax.evals == 2  # 2 unique genomes, not 4 jobs
        assert all(ind.fitness_evaluated for ind in pop)
        assert pop[0].get_fitness() == pop[1].get_fitness() == pop[2].get_fitness()

    def test_cache_answers_next_generation_without_jobs(self):
        CountingOneMax.evals = 0
        g = {"S_1": (1, 1, 1, 0, 0, 0), "S_2": (0, 0, 0, 1, 1, 1)}
        inds = [CountingOneMax(genes=g, additional_parameters={"nodes": (4, 4)})]
        with DistributedPopulation(
            CountingOneMax,
            individual_list=inds,
            additional_parameters={"nodes": (4, 4)},
            port=0,
        ) as pop:
            _, port = pop.broker_address
            stop, _ = _start_worker_thread(CountingOneMax, port)
            try:
                pop.evaluate()
                assert CountingOneMax.evals == 1
                # next generation re-derives the same genome: cache, no wire
                stop.set()  # no workers alive — a shipped job would hang
                child = pop.spawn(genes=g)
                nxt = pop.clone_with([child])
                nxt.job_timeout = 5.0
                nxt.evaluate()
                assert child.get_fitness() == pop[0].get_fitness()
                assert CountingOneMax.evals == 1
            finally:
                stop.set()


class TestBrokerEdgeCases:
    def test_gather_timeout_applies_partial_results(self):
        """A straggler timeout keeps the fitnesses that DID arrive."""
        with DistributedPopulation(
            SlowOneMax, size=3, seed=8, port=0, job_timeout=2.5,
            additional_parameters={"delay": 0.2},
        ) as pop:
            _, port = pop.broker_address
            # One worker, capacity 1, allowed to finish exactly TWO jobs,
            # then it exits — the third job can never finish.
            t = threading.Thread(
                target=_run_worker,
                args=(SlowOneMax, port),
                kwargs={"max_jobs": 2},
                daemon=True,
            )
            t.start()
            with pytest.raises(TimeoutError):
                pop.evaluate()
            evaluated = [ind for ind in pop if ind.fitness_evaluated]
            assert len(evaluated) == 2  # finished work survived the timeout
            # retry reships ONLY the unfinished individual
            stop, _ = _start_worker_thread(SlowOneMax, port)
            try:
                assert pop.evaluate() == 1
                assert all(ind.fitness_evaluated for ind in pop)
            finally:
                stop.set()

    def test_oversized_payload_raises_in_submit(self):
        """Size validation happens in the caller's thread, not the loop."""
        from gentun_tpu.distributed.protocol import MAX_MESSAGE_BYTES, ProtocolError

        broker = JobBroker(port=0).start()
        try:
            huge = {"genes": {"S_1": "x" * (MAX_MESSAGE_BYTES + 10)}, "additional_parameters": {}}
            with pytest.raises(ProtocolError):
                broker.submit({"j": huge})
            assert broker._payloads == {}  # nothing was enqueued
        finally:
            broker.stop()

    def test_exact_max_size_frame_round_trips(self):
        """A payload of exactly MAX_MESSAGE_BYTES passes encode() and must
        survive decode() too — the framing newline no longer tips the frame
        over the size check (ADVICE r3 boundary fix)."""
        from gentun_tpu.distributed.protocol import MAX_MESSAGE_BYTES, decode, encode

        probe = {"type": "result", "job_id": "j", "fitness": 1.0, "pad": ""}
        overhead = len(encode(probe)) - 1  # minus the newline
        probe["pad"] = "x" * (MAX_MESSAGE_BYTES - overhead)
        frame = encode(probe)
        assert len(frame) == MAX_MESSAGE_BYTES + 1  # payload + newline
        assert decode(frame)["pad"] == probe["pad"]

    def test_large_batch_splits_into_multiple_frames_and_completes(self):
        """Batches over the soft cap arrive as several `jobs` frames; a real
        worker consumes them frame by frame and every job completes."""
        from gentun_tpu.distributed.protocol import MAX_MESSAGE_BYTES

        # ~1.3 MB of padding per job => 4 jobs exceed the 2 MB soft cap.
        pad = "p" * (MAX_MESSAGE_BYTES // 3)
        inds = [
            OneMax(genes={"S_1": (1, 0, i % 2, 0, 1, 0), "S_2": (1,) * 6},
                   additional_parameters={"nodes": (4, 4), "pad": pad})
            for i in range(4)
        ]
        with DistributedPopulation(
            OneMax,
            individual_list=inds,
            additional_parameters={"nodes": (4, 4), "pad": pad},
            port=0,
            job_timeout=30.0,
        ) as pop:
            _, port = pop.broker_address
            stop, _ = _start_worker_thread(OneMax, port, capacity=4)
            try:
                pop.evaluate()
                assert all(ind.fitness_evaluated for ind in pop)
            finally:
                stop.set()


class TestWorkerCli:
    def test_module_entrypoint_serves_jobs(self):
        """`python -m gentun_tpu.distributed.worker` is a functioning worker:
        it loads its own dataset, serves the master's jobs, and exits at
        --max-jobs."""
        import subprocess
        import sys

        from gentun_tpu import BoostingIndividual

        job_timeout = 120.0
        with DistributedPopulation(
            BoostingIndividual, size=2, seed=9, port=0,
            additional_parameters={"kfold": 2},
            job_timeout=job_timeout,
        ) as pop:
            _, port = pop.broker_address
            repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            # One OpenMP thread for the child: HistGradientBoosting opens a
            # team over every core at each of its many small loops, and where
            # six test workers already hold the cores every barrier of that
            # team waits on threads that are not running — two 6 s jobs then
            # outlast the 120 s barrier (reproduced with six BLAS loops).
            env = dict(os.environ, PYTHONPATH=repo, OMP_NUM_THREADS="1")
            proc = subprocess.Popen(
                [sys.executable, "-m", "gentun_tpu.distributed.worker",
                 "--host", "127.0.0.1", "--port", str(port),
                 "--species", "boosting", "--dataset", "uci-binary",
                 "--max-jobs", "2"],
                env=env, cwd=repo,
            )
            try:
                pop.evaluate()
                assert all(ind.fitness_evaluated for ind in pop)
                assert all(0.0 <= ind.get_fitness() <= 1.0 for ind in pop)
                assert proc.wait(timeout=job_timeout) == 0  # exited cleanly at --max-jobs
            finally:
                if proc.poll() is None:
                    proc.kill()


class TestMasterCrashResume:
    """SURVEY.md §5: 'Master death is unrecoverable' in the reference — the
    rebuild beats it: checkpoint + DistributedPopulation survive a master
    crash, workers reconnect to the reborn master, and the completed search
    is bit-compatible with an uninterrupted one (VERDICT r1 item #8)."""

    def test_master_crash_resume_completes_bit_compatibly(self, tmp_path):
        from gentun_tpu.utils import Checkpointer

        path = str(tmp_path / "distributed-ckpt.json")
        # A FIXED port (picked free) so the surviving worker's reconnect
        # loop can find the reborn master; ephemeral port=0 would change.
        probe = socket.socket()
        probe.bind(("127.0.0.1", 0))
        port = probe.getsockname()[1]
        probe.close()

        # Uninterrupted reference: single-process, same seeds (OneMax fitness
        # is pure, so local and remote evaluation agree exactly).
        ga_full = GeneticAlgorithm(Population(OneMax, *DATA, size=6, seed=42), seed=7)
        ga_full.run(5)

        # Act 1: distributed master + worker; checkpoint; crash after gen 2.
        pop_a = DistributedPopulation(OneMax, size=6, seed=42, host="127.0.0.1", port=port)
        stop, _ = _start_worker_thread(OneMax, port)
        try:
            ga_a = GeneticAlgorithm(pop_a, seed=7)
            ga_a.set_checkpointer(Checkpointer(path))
            ga_a.run(2)
        finally:
            # the "crash": broker listener dies with the master process;
            # the worker survives and enters its reconnect loop
            ga_a.population.close()
            pop_a.close()
        del ga_a, pop_a

        # Act 2: reborn master on the SAME port resumes from the checkpoint.
        pop_b = DistributedPopulation(OneMax, size=6, seed=0, host="127.0.0.1", port=port)
        try:
            ga_b = GeneticAlgorithm(pop_b, seed=0)
            assert Checkpointer(path).resume(ga_b)
            assert ga_b.generation == 2
            ga_b.run(3)  # worker reconnected and served these generations
            full = [(ind.get_genes(), ind.get_fitness()) for ind in ga_full.population]
            resumed = [(ind.get_genes(), ind.get_fitness()) for ind in ga_b.population]
            assert full == resumed
        finally:
            ga_b.population.close()
            pop_b.close()
            stop.set()


class TestBrokerOwnership:
    def test_close_on_clone_stops_embedded_broker(self):
        pop = DistributedPopulation(OneMax, size=2, seed=0, port=0)
        clone = pop.clone_with([pop[0].copy()])
        assert clone._owns_broker  # co-owns: GA holds only clones after gen 1
        clone.close()
        assert not pop.broker._started.is_set()
        pop.close()  # idempotent: original closing after the clone is safe

    def test_external_broker_never_stopped_by_clones(self):
        broker = JobBroker(port=0).start()
        try:
            pop = DistributedPopulation(OneMax, size=2, seed=0, broker=broker)
            clone = pop.clone_with([pop[0].copy()])
            assert not clone._owns_broker
            clone.close()
            pop.close()
            assert broker._started.is_set()  # still running
        finally:
            broker.stop()


class TestFleetChips:
    """VERDICT r3 item 3: the per-chip metric divides by the fleet's chips."""

    def test_logged_metric_divides_by_advertised_chips(self):
        with DistributedPopulation(
            SlowOneMax, size=4, seed=0, port=0,
            additional_parameters={"delay": 0.1},
        ) as pop:
            _, port = pop.broker_address
            stop = threading.Event()
            threading.Thread(
                target=lambda: GentunClient(
                    SlowOneMax, *DATA, port=port, capacity=4, n_chips=4,
                    heartbeat_interval=0.2, reconnect_delay=0.1,
                ).work(stop_event=stop),
                daemon=True,
            ).start()
            try:
                ga = GeneticAlgorithm(pop, seed=0)
                ga.evolve_population()
                rec = ga.history[0]
                assert rec["n_chips"] == 4
                # the logged metric is evaluated/hour divided by the fleet's
                # chip total, not by the master's (jax-less) local count of 1
                per_cluster = rec["evaluated"] / (rec["eval_wall_s"] / 3600.0)
                assert rec["individuals_per_hour_per_chip"] == pytest.approx(
                    per_cluster / 4, rel=0.05
                )
            finally:
                stop.set()

    def test_non_jax_species_advertises_one_chip(self):
        with DistributedPopulation(OneMax, size=2, seed=0, port=0) as pop:
            _, port = pop.broker_address
            stop, _ = _start_worker_thread(OneMax, port)
            try:
                pop.evaluate()
                assert pop.eval_stats["n_chips"] == 1
                assert pop.broker.fleet_chips() == 1
            finally:
                stop.set()

    def test_fleet_chips_sums_across_workers(self):
        with DistributedPopulation(OneMax, size=4, seed=0, port=0) as pop:
            _, port = pop.broker_address
            stop = threading.Event()
            for chips in (3, 5):
                threading.Thread(
                    target=lambda c=chips: GentunClient(
                        OneMax, *DATA, port=port, capacity=2, n_chips=c,
                        heartbeat_interval=0.2, reconnect_delay=0.1,
                    ).work(stop_event=stop),
                    daemon=True,
                ).start()
            try:
                deadline = time.monotonic() + 5.0
                while pop.broker.fleet_chips() != 8 and time.monotonic() < deadline:
                    time.sleep(0.05)
                assert pop.broker.fleet_chips() == 8
                pop.evaluate()
                assert pop.eval_stats["n_chips"] == 8
            finally:
                stop.set()

    def test_worker_exiting_after_final_result_still_counts(self):
        """ADVICE r4: the per-chip denominator must survive a worker that
        delivers its last result and disconnects before the post-sweep
        snapshot.  A --max-jobs worker exits the instant its results are
        sent; with only the end-of-sweep fleet_chips() its 4 chips would
        collapse to 1."""
        with DistributedPopulation(OneMax, size=4, seed=0, port=0) as pop:
            _, port = pop.broker_address
            t = threading.Thread(
                target=lambda: GentunClient(
                    OneMax, *DATA, port=port, capacity=4, n_chips=4,
                    heartbeat_interval=0.2, reconnect_delay=0.1,
                ).work(max_jobs=4),
                daemon=True,
            )
            t.start()
            pop.evaluate()
            t.join(timeout=10)  # worker already gone (or going)
            assert pop.eval_stats["n_chips"] == 4

    def test_single_process_record_unchanged(self):
        """Non-distributed populations keep the local-chip denominator
        (whatever the already-initialized backend reports in this process —
        other tests in the suite may have touched the 8-device CPU mesh)."""
        from gentun_tpu.algorithms import _initialized_chip_count

        pop = Population(OneMax, *DATA, size=3, seed=0)
        ga = GeneticAlgorithm(pop, seed=0)
        ga.evolve_population()
        assert ga.history[0]["n_chips"] == _initialized_chip_count()


class TestDistributedFitnessStore:
    """VERDICT r3 item 7: the flagship path reuses cross-run measurements."""

    def test_second_run_over_same_genomes_ships_zero_jobs(self, tmp_path):
        store = str(tmp_path / "onemax.fitness.json")
        genes = None
        # First search: evaluates over a real worker, saves the store on close.
        with DistributedPopulation(
            OneMax, size=4, seed=11, port=0, fitness_store=store,
        ) as pop:
            _, port = pop.broker_address
            stop, _ = _start_worker_thread(OneMax, port)
            try:
                shipped = pop.evaluate()
                assert shipped > 0
                genes = [ind.get_genes() for ind in pop]
                fits = [ind.get_fitness() for ind in pop]
            finally:
                stop.set()
        assert os.path.exists(store)

        # Second search, same genomes, NO workers connected: every fitness
        # must come from the store — evaluate() ships zero jobs (it would
        # block forever otherwise, so the 10s timeout doubles as the proof).
        inds = [OneMax(genes=g) for g in genes]
        with DistributedPopulation(
            OneMax, individual_list=inds, fitness_store=store, port=0,
            job_timeout=10.0,
        ) as pop2:
            assert pop2.evaluate() == 0
            assert [ind.get_fitness() for ind in pop2] == fits

    def test_worker_side_store_answers_without_training(self, tmp_path):
        """VERDICT r4 item 7: a WORKER given --fitness-store answers repeated
        jobs from the store instead of retraining.  The stored fitness is a
        sentinel no real OneMax evaluation could produce, so the returned
        value proves the store (not training) answered."""
        from gentun_tpu.utils.fitness_store import save_fitness_cache

        store = str(tmp_path / "worker.fitness.json")
        probe = OneMax(genes={"S_1": (1, 0, 1, 1, 1, 1), "S_2": (0, 1, 0, 0, 0, 0)})
        sentinel = 4242.5  # OneMax fitness is a bit count — can't be this
        save_fitness_cache({probe.cache_key(): sentinel}, store)

        # Master WITHOUT a store: reuse must happen on the worker side.
        with DistributedPopulation(
            OneMax, individual_list=[OneMax(genes=probe.get_genes())], port=0,
        ) as pop:
            _, port = pop.broker_address
            stop, _ = _start_worker_thread(OneMax, port, fitness_store=store)
            try:
                assert pop.evaluate() == 1  # the job WAS shipped...
                assert pop[0].get_fitness() == sentinel  # ...but not trained
            finally:
                stop.set()

    def test_worker_store_refused_for_multihost(self, tmp_path):
        with pytest.raises(ValueError, match="multihost"):
            GentunClient(OneMax, *DATA, multihost=True,
                         fitness_store=str(tmp_path / "x.json"))

    def test_in_memory_measurement_beats_stored_value(self, tmp_path):
        from gentun_tpu.utils.fitness_store import save_fitness_cache

        store = str(tmp_path / "seed.fitness.json")
        probe = OneMax(genes={"S_1": (1,) * 6, "S_2": (0,) * 6})
        save_fitness_cache({probe.cache_key(): -99.0}, store)
        live = {probe.cache_key(): 6.0}
        pop = DistributedPopulation(
            OneMax, individual_list=[OneMax(genes=probe.get_genes())],
            fitness_store=store, fitness_cache=live, port=0,
        )
        try:
            assert pop.evaluate() == 0
            assert pop[0].get_fitness() == 6.0
        finally:
            pop.close()

    def test_clone_carries_store_and_close_saves(self, tmp_path):
        from gentun_tpu.utils.fitness_store import load_fitness_cache

        store = str(tmp_path / "clone.fitness.json")
        pop = DistributedPopulation(OneMax, size=2, seed=3, port=0, fitness_store=store)
        _, port = pop.broker_address
        stop, _ = _start_worker_thread(OneMax, port)
        try:
            pop.evaluate()
            clone = pop.clone_with([ind.copy() for ind in pop])
            assert clone.fitness_store == store
            clone.close()  # the GA hands back clones; closing one must save
            assert len(load_fitness_cache(store)) > 0
        finally:
            stop.set()
            pop.close()


class TestBackendAdvertisement:
    """ADVICE r3: a mixed fleet scoring one generation with two different
    estimators must be warned about at the master."""

    def test_heterogeneous_fleet_warns(self, caplog):
        class BackendA(OneMax):
            model_cls = type("XgboostModel", (), {})

        class BackendB(OneMax):
            model_cls = type("BoostingModel", (), {})

        import logging as _logging

        with DistributedPopulation(OneMax, size=2, seed=0, port=0) as pop:
            _, port = pop.broker_address
            stop = threading.Event()
            with caplog.at_level(_logging.WARNING, logger="gentun_tpu.distributed"):
                for species in (BackendA, BackendB):
                    threading.Thread(
                        target=lambda s=species: GentunClient(
                            s, *DATA, port=port, heartbeat_interval=0.2,
                            reconnect_delay=0.1,
                        ).work(stop_event=stop),
                        daemon=True,
                    ).start()
                try:
                    deadline = time.monotonic() + 5.0
                    while time.monotonic() < deadline and not any(
                        "heterogeneous fitness backends" in r.message for r in caplog.records
                    ):
                        time.sleep(0.05)
                    assert any(
                        "heterogeneous fitness backends" in r.message for r in caplog.records
                    )
                finally:
                    stop.set()

    def test_homogeneous_fleet_quiet(self, caplog):
        import logging as _logging

        with DistributedPopulation(OneMax, size=2, seed=0, port=0) as pop:
            _, port = pop.broker_address
            stops = []
            with caplog.at_level(_logging.WARNING, logger="gentun_tpu.distributed"):
                try:
                    for _ in range(2):
                        stops.append(_start_worker_thread(OneMax, port)[0])
                    pop.evaluate()
                    assert not any(
                        "heterogeneous fitness backends" in r.message for r in caplog.records
                    )
                finally:
                    for s in stops:
                        s.set()


class TestWorkerCliGuards:
    """ADVICE r3: non-positive --n must be rejected loudly, not yield an
    empty or silently truncated dataset."""

    @pytest.mark.parametrize("bad_n", ["0", "-5"])
    def test_non_positive_n_rejected(self, bad_n):
        from gentun_tpu.distributed.worker import main as worker_main

        with pytest.raises(SystemExit, match="must be positive"):
            worker_main([
                "--species", "boosting", "--dataset", "uci-binary",
                "--n", bad_n, "--max-jobs", "1",
            ])


class TestFinalResultsNotLostOnExit:
    """Regression (found by the multihost CNN e2e test): a worker exiting
    right after its last batch used to close the socket with unread
    broker frames in its receive buffer, turning close() into an RST that
    destroyed the still-in-flight result frames.  Heartbeat replies are
    gone and the clean-exit path now FIN-drains (``_graceful_close``), so
    every result of the final batch must arrive."""

    def test_worker_exit_after_final_batch_delivers_all_results(self):
        with DistributedPopulation(
            SlowOneMax, size=6, seed=2, port=0,
            additional_parameters={"delay": 0.5}, job_timeout=60.0,
        ) as pop:
            _, port = pop.broker_address
            # Tiny heartbeat interval: many pings pile up during the slow
            # batch (the old pong replies would have sat unread); max_jobs
            # makes the worker exit the instant the batch is replied.
            worker = GentunClient(
                SlowOneMax, *DATA, port=port, capacity=6,
                heartbeat_interval=0.02, reconnect_delay=0.1,
            )
            t = threading.Thread(target=lambda: worker.work(max_jobs=6), daemon=True)
            t.start()
            assert pop.evaluate() == 6  # every result of the final batch arrived
            t.join(timeout=10.0)
            assert not t.is_alive()


class TestDistributedFitnessPurity:
    """Distributed evaluation must be bit-identical to local evaluation.

    The worker trains whatever job batch the broker hands it (capacity
    chunks, arrival order) — compositions the local ``evaluate()`` never
    produces.  Content-hash PRNG keys (``models/evaluation.genome_hashes``)
    make fitness a pure function of (architecture, config, seed), so the
    transport layer cannot move a measurement."""

    def test_capacity_chunked_worker_matches_local_bitwise(self):
        from gentun_tpu import GeneticCnnIndividual

        rng = np.random.default_rng(3)
        protos = rng.normal(size=(3, 8, 8, 1)).astype(np.float32)
        yv = rng.integers(0, 3, size=96).astype(np.int32)
        xv = (protos[yv] + 0.25 * rng.normal(size=(96, 8, 8, 1))).astype(np.float32)
        params = dict(nodes=(3,), kernels_per_layer=(6,), kfold=2, epochs=(1,),
                      learning_rate=(0.05,), batch_size=32, dense_units=16,
                      compute_dtype="float32", seed=0)

        local = Population(GeneticCnnIndividual, x_train=xv, y_train=yv,
                           size=6, seed=5, additional_parameters=params)
        local.evaluate()
        local_fits = {ind.cache_key(): ind.get_fitness() for ind in local}

        # capacity=2: the worker trains 2-wide chunks — different program
        # shapes AND different batch compositions than the local one-shot
        with DistributedPopulation(GeneticCnnIndividual, size=6, seed=5,
                                   additional_parameters=params, port=0) as dist:
            _, port = dist.broker_address
            stop = threading.Event()
            t = threading.Thread(
                target=lambda: GentunClient(
                    GeneticCnnIndividual, xv, yv, host="127.0.0.1", port=port,
                    capacity=2, heartbeat_interval=0.2, reconnect_delay=0.1,
                ).work(stop_event=stop),
                daemon=True,
            )
            t.start()
            try:
                dist.evaluate()
                assert all(ind.fitness_evaluated for ind in dist)
                for ind in dist:
                    assert ind.get_fitness() == local_fits[ind.cache_key()], (
                        "distributed fitness differs from local for the same "
                        "architecture under the same config+seed"
                    )
            finally:
                stop.set()
                t.join(timeout=15.0)


class TestCleanShutdown:
    def test_stop_drains_connection_handlers(self):
        """stop() must cancel and DRAIN the per-connection handler
        coroutines before stopping the loop.  Stopping with handlers
        parked on readline() left pending tasks (asyncio logged "Task was
        destroyed but it is pending!" at master exit) and — the
        deterministic symptom asserted here — skipped the handlers'
        finally-block cleanup, leaving the dead connection registered in
        the worker table after shutdown."""
        import json
        import socket

        broker = JobBroker(port=0).start()
        host, port = broker.address
        s = socket.create_connection((host, port))
        try:
            s.sendall((json.dumps({"type": "hello", "worker_id": "w1",
                                   "token": None, "capacity": 1,
                                   "n_chips": 1, "backend": "test"}) + "\n").encode())
            deadline = time.monotonic() + 5.0
            # fleet_chips() floors at 1, so wait on the worker table itself
            while not broker._workers and time.monotonic() < deadline:
                time.sleep(0.05)  # handler task now parked on readline()
            assert broker._workers  # hello processed, handler registered
            broker.stop()
            # the handler's finally ran during shutdown: worker table empty
            assert broker._workers == {}
        finally:
            s.close()


class TestReconnectBackoff:
    """The worker's one connection redials under a capped, jittered backoff."""

    def test_reconnect_backoff_grows_to_the_cap_and_a_handshake_resets_it(self, monkeypatch):
        from gentun_tpu.distributed import client as client_module

        delays, resets = [], []
        next_delay, reset = client_module._ReconnectBackoff.next_delay, client_module._ReconnectBackoff.reset

        def watched_delay(self):
            delays.append(next_delay(self))
            return delays[-1]

        def watched_reset(self):
            resets.append(len(delays))
            reset(self)

        monkeypatch.setattr(client_module._ReconnectBackoff, "next_delay", watched_delay)
        monkeypatch.setattr(client_module._ReconnectBackoff, "reset", watched_reset)
        with socket.socket() as s:
            s.bind(("127.0.0.1", 0))
            port = s.getsockname()[1]  # free, and nobody listens on it yet
        client = GentunClient(OneMax, *DATA, port=port, capacity=1, heartbeat_interval=0.2, reconnect_delay=0.01,
                              reconnect_max_delay=0.08, worker_id="backoff-w0")
        stop = threading.Event()
        thread = threading.Thread(target=client.work, kwargs={"stop_event": stop}, daemon=True)
        thread.start()
        broker = None
        try:
            deadline = time.monotonic() + 10.0
            while len(delays) < 8 and time.monotonic() < deadline:
                time.sleep(0.02)
            refused = list(delays)
            assert len(refused) >= 8 and not resets, "no broker: every dial is refused, nothing re-arms the delay"
            assert refused[0] == 0.01 and all(0.01 <= d <= 0.08 for d in refused) and max(refused) > 0.03
            assert 0.08 in refused, "the delay reaches the cap and stays under it"
            broker = JobBroker(host="127.0.0.1", port=port).start()
            while not resets and time.monotonic() < deadline:
                time.sleep(0.02)
            assert resets, "a completed handshake re-arms the base delay"
            at_handshake = len(delays)
            broker.stop()
            broker = None
            while len(delays) == at_handshake and time.monotonic() < deadline:
                time.sleep(0.02)
            assert delays[at_handshake] == 0.01, "the first redial after a handshake waits the base delay again"
        finally:
            stop.set()
            client.shutdown()
            if broker is not None:
                broker.stop()
            thread.join(timeout=10.0)

    def test_backoff_seed_is_the_workers_own(self, monkeypatch):
        """Decorrelated jitter must not march in lockstep across a fleet: one
        worker's sequence is its own (reproducible), another's differs, and
        ``work()`` seeds its connection's backoff from the worker id."""
        from gentun_tpu.distributed import client as client_module

        def sequence(seed):
            backoff = client_module._ReconnectBackoff(0.05, 5.0, seed)
            return [backoff.next_delay() for _ in range(6)]

        assert sequence("w0") == sequence("w0") and sequence("w0") != sequence("w1")
        assert sequence("w0")[0] == 0.05 and max(sequence("w0")) <= 5.0
        seeds = []
        init = client_module._ReconnectBackoff.__init__
        monkeypatch.setattr(client_module._ReconnectBackoff, "__init__",
                            lambda self, base, cap, seed: (seeds.append((base, cap, seed)), init(self, base, cap, seed))[1])
        with DistributedPopulation(OneMax, size=2, seed=0, port=0) as pop:
            client = GentunClient(OneMax, *DATA, port=pop.broker_address[1], reconnect_delay=0.07,
                                  reconnect_max_delay=3.0, worker_id="seeded-w7")
            assert client.work(max_jobs=0) == 0
        assert seeds == [(0.07, 3.0, "seeded-w7")]


class TestOneBrokerServesAFleet:
    """Horizontal broker sharding went with PR 45: no party takes a list of brokers."""

    def test_session_client_refuses_broker_urls(self):
        from gentun_tpu.distributed.sessions import SessionClient

        with pytest.raises(TypeError, match="broker_urls"):
            SessionClient(broker_urls=["127.0.0.1:1", "127.0.0.1:2"])
        with pytest.raises(TypeError, match="host"):
            SessionClient()  # host and port are required

    def test_distributed_population_refuses_broker_urls(self):
        with pytest.raises(TypeError, match="broker_urls"):
            DistributedPopulation(OneMax, size=2, seed=0, broker_urls=["127.0.0.1:1", "127.0.0.1:2"])

    def test_worker_cli_rejects_broker_urls(self, capsys):
        from gentun_tpu.distributed.worker import main as worker_main

        with pytest.raises(SystemExit) as refused:
            worker_main(["--species", "boosting", "--dataset", "uci-binary", "--broker-urls", "127.0.0.1:1,127.0.0.1:2"])
        assert refused.value.code == 2 and "--broker-urls" in capsys.readouterr().err
