"""Seeded arrival schedule of log shippers (Filebeat, Logstash, Fluent
Bit agents) flushing to a parse sidecar.

Each agent tails the log of one web server of a fleet behind a
round-robin balancer (NGINX ``upstream``'s default method), so every
agent logs at the same rate.  An agent's flush timer fires every
``flush_s`` from a phase of its own (Fluent Bit's ``flush`` default is
1 s), or sooner when its buffer reaches ``max_lines`` (the coalescer's
4,096-line window), so a flush holds Poisson(rate x interval) lines, at
least one.

Every seed gets the same work: the flush instants and sizes come from
the traffic's fixed ``work_seed``; the run seed decides which session
carries which agent, and (in ``loadgen.build_requests``) the lines.
Nothing here imports the program.
"""
import numpy as np


def agent_rates(p):
    """Lines per second of each agent: the offered rate, shared evenly."""
    return np.full(p["agents"], p["lines_per_s"] / p["agents"])


def schedule(p, seed, seconds):
    """[(due_s, agent, n_lines)] over ``[0, seconds)``, sorted by due
    time.  The flush sizes and instants come from the traffic's fixed
    ``work_seed``, so every run seed offers the same work; the run seed
    decides which session carries which agent's stream."""
    fixed = np.random.default_rng([p["work_seed"], 0x73686970])
    out = []
    for rank, rate in enumerate(agent_rates(p)):
        interval = min(p["flush_s"], p["max_lines"] / rate)
        t = fixed.uniform(0.0, interval)
        while t < seconds:
            n = int(np.clip(fixed.poisson(rate * interval), 1, p["max_lines"]))
            out.append((float(t), rank, n))
            t += interval
    label = np.random.default_rng([int(seed) & (2**63 - 1), 0x6167]).permutation(
        p["agents"])
    return sorted((t, int(label[rank]), n) for t, rank, n in out)
