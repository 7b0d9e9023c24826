"""Driver: open-loop serving through a live hop.

Set-up makes GPT-2's weights and a LiGO operator on the device from the
seed, warms every program the window uses — both models' prefill, decode
and insert programs, the re-prefill that migrates live sessions, and the
growth — and builds the ``ServingEngine`` with its ``HopController``.

The window offers the mix's requests at their due times (an open loop: a
request is submitted when it is due, whether or not the engine keeps up),
drives ``engine.step()`` and, a third of the way in, ``hop.begin()``, with
``hop.poll()`` after every step. The harness's clock stamps every step's
return; a request's tokens are stamped with the return of the step that
produced them. After the last request is due, the engine drains for at
most ``drain_s``; a request unfinished then counts as failed.

- ``ttft_p95_ms``: 95th percentile over the requests due in the window of
  first token minus the time the request was due;
- ``itl_p95_ms``: 95th percentile over every gap between consecutive
  tokens of every request, gaps across the hop included;
- ``hop.stall_ms``: the longest wait for a step's return while the engine
  had work, from the previous return (or from when work reached an idle
  engine): idle stretches between arrivals do not count.

The check replays a sample of the finished requests, drawn from the seed
with the longest among them, through the plain reference: for every
served token, the model that served it (GPT-2 before the swap, the grown
model after it, grown by the reference from the same operator) runs over
the prompt and the tokens before it, and the gap by which the served
token's logit lies below the reference's best is taken. Greedy decoding
serves the best token, so the widest gap measures how far the program's
arithmetic strays.
"""
from __future__ import annotations

import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.lib import reference as R
from benchmarks.chip.lib import traffic, weights
from benchmarks.chip.lib.programs import program_configs

SALT_PARAMS, SALT_OP, SALT_TRAFFIC, SALT_SAMPLE = 21, 22, 23, 24


def make_inputs(ctx):
    cfg = ctx.config
    params = weights.params_on_device(ctx.key(SALT_PARAMS), cfg["src"],
                                      weights.DTYPES[cfg["dtype"]])
    op = jax.jit(lambda k: R.init_operator(k, cfg["src"], cfg["dst"]))(
        ctx.key(SALT_OP))
    return params, op


def new_engine(state, mix: Dict):
    from repro.serving import HopController, ServingEngine
    eng = ServingEngine(state["params"], state["cfg1"], slots=mix["slots"],
                        prompt_budget=mix["prompt_budget"],
                        gen_budget=mix["gen_budget"],
                        queue_capacity=mix["queue_capacity"],
                        block_size=mix["block_size"])
    hop = HopController(eng, state["cfg2"], state["op"],
                        timeout=mix["hop_timeout_s"])
    return eng, hop


def warm(state, mix: Dict, log) -> None:
    """Every program the window runs, at the window's shapes: each model's
    engine admits and decodes a request at the prompt budget, the grown
    engine re-prefills a live session (the hop's cache migration), and the
    growth runs once."""
    from repro.core.plan import plan_for
    from repro.serving import ServingEngine
    cfg1, cfg2 = state["cfg1"], state["cfg2"]
    plan = plan_for(cfg1, cfg2, state["params"])
    grown = plan.executor()(state["op"], state["params"])
    k, n = plan.kernel_groups()
    log(f"plan: {k}/{n} groups on the fused kernels")
    prompt = list(range(1, mix["prompt_budget"] + 1))
    for params, cfg in ((grown, cfg2), (state["params"], cfg1)):
        eng = ServingEngine(params, cfg, slots=mix["slots"],
                            prompt_budget=mix["prompt_budget"],
                            gen_budget=mix["gen_budget"],
                            block_size=mix["block_size"])
        eng.submit(prompt, max_new=3)
        eng.step()
        eng.step()
        if cfg is cfg1:        # migrate a live session, as the hop does
            jax.block_until_ready(eng.reprefill_state(grown, cfg2))
        eng.run()
        del eng
    del grown


def setup(ctx):
    mix = ctx.traffic
    cfg1, cfg2 = program_configs(ctx.config, ctx.log)
    params, op = make_inputs(ctx)
    jax.block_until_ready((params, op))
    state = {"cfg1": cfg1, "cfg2": cfg2, "params": params, "op": op}
    state["requests"] = traffic.schedule(
        mix, ctx.seconds, ctx.np_seed(SALT_TRAFFIC),
        ctx.config["src"]["vocab_size"])
    ctx.mark("init")
    with ctx.span("bench.warm"):
        warm(state, mix, ctx.log)
        eng, hop = new_engine(state, mix)
        hop.warm()
    state["engine"], state["hop"] = eng, hop
    return state


def serve(ctx, eng, hop, requests: List, seconds: float, mix: Dict) -> Dict:
    """Drive the engine through one window of ``requests``; returns what
    the harness's clock saw."""
    t0 = time.perf_counter()
    n, i = len(requests), 0
    subs, stamps, steps = [None] * n, [[] for _ in range(n)], []
    seen = [0] * n
    pending = set()
    hop_at = mix["hop_at"] * seconds
    swap_len: Dict[int, int] = {}
    t_begin = t_swap = None
    queue_third = queue_end = None
    deadline = None
    late = 0.0                      # how late the generator submitted
    # the longest wait for a step's return while the engine had work: from
    # the previous return, or from when work arrived at an idle engine
    stall, busy_since = 0.0, None
    while True:
        now = time.perf_counter() - t0
        while i < n and requests[i].due <= now:
            late = max(late, now - requests[i].due)
            with ctx.span("bench.submit"):
                subs[i] = eng.submit(requests[i].prompt, requests[i].max_new)
            pending.add(i)
            i += 1
        if t_begin is None and now >= hop_at:
            with ctx.span("bench.hop_begin"):
                hop.begin()
            t_begin = now
        if queue_third is None and now >= seconds / 3:
            queue_third = len(eng.queue)
        if queue_end is None and i == n:
            queue_end = len(eng.queue)          # as the last one is due
        if eng.has_work():
            if busy_since is None:
                busy_since = now
            with ctx.span("bench.engine_step"):
                eng.step()
            t = time.perf_counter() - t0
            steps.append(t)
            stall = max(stall, t - busy_since)
            busy_since = t
            for j in list(pending):
                r = subs[j]
                k = len(r.tokens)
                if k > seen[j]:
                    stamps[j].extend([t] * (k - seen[j]))
                    seen[j] = k
                if r.status in ("done", "rejected", "dropped"):
                    pending.discard(j)
        else:
            busy_since = None
            if i < n:
                time.sleep(min(max(requests[i].due - now, 0.0), 1e-3))
        if t_begin is not None and t_swap is None:
            with ctx.span("bench.hop_poll"):
                settled = hop.poll()
            if settled and hop.completed:
                t_swap = time.perf_counter() - t0
                for j in pending:
                    swap_len[j] = len(subs[j].tokens)
        if i == n and deadline is None:
            deadline = max(now, seconds) + mix["drain_s"]
        if i == n and not pending and (t_swap is not None or hop.failed):
            break
        if deadline is not None and now > deadline:
            break
    done_t = time.perf_counter() - t0
    return {"subs": subs, "stamps": stamps, "steps": steps,
            "swap_len": swap_len, "t_begin": t_begin, "t_swap": t_swap,
            "queue_third": queue_third, "queue_end": queue_end,
            "hop_completed": hop.completed, "elapsed_s": done_t,
            "late_ms": late * 1e3, "stall_s": stall}


def summarize(requests, seen: Dict) -> Dict:
    """End-to-end numbers from the harness's stamps."""
    ttft, itl, failed = [], [], 0
    for req, sub, st in zip(requests, seen["subs"], seen["stamps"]):
        if sub is None or sub.status != "done" or not st:
            failed += 1
            continue
        ttft.append((st[0] - req.due) * 1e3)
        itl.extend((b - a) * 1e3 for a, b in zip(st, st[1:]))
    out = {"attempted": len(requests), "failed": failed,
           "tokens": sum(len(s) for s in seen["stamps"]),
           "stall_ms": seen["stall_s"] * 1e3, "ttft_ms": ttft, "itl_ms": itl,
           "queue_third": seen["queue_third"],
           "queue_end": seen["queue_end"], "elapsed_s": seen["elapsed_s"],
           "hop_completed": seen["hop_completed"],
           "late_ms": seen["late_ms"]}
    if ttft:
        out["ttft_p95_ms"] = float(np.percentile(ttft, 95))
    if itl:
        out["itl_p95_ms"] = float(np.percentile(itl, 95))
    if not seen["hop_completed"]:
        out["failed"] = max(failed, 1)
    out.update(work(seen))
    out["t_swap"] = seen["t_swap"]
    return out


def work(seen: Dict) -> Dict:
    """What the device was asked to do, by model ("src" before the swap,
    "dst" after): the true length of every prefill (admissions, and the
    hop's re-prefill of each live session's history) and the live length
    of every decoded token."""
    prefills, decodes = [], []
    for j, sub in enumerate(seen["subs"]):
        if sub is None or not sub.tokens:
            continue
        prompt, toks, before = served(seen, j)
        P = len(prompt)
        prefills.append(("src" if before > 0 else "dst", P))
        if j in seen["swap_len"]:
            prefills.append(("dst", P + seen["swap_len"][j] - 1))
        for k in range(1, len(toks)):
            decodes.append(("src" if k < before else "dst", P + k))
    return {"prefills": prefills, "decodes": decodes}


def window(ctx, state) -> Dict:
    seen = serve(ctx, state["engine"], state["hop"], state["requests"],
                 ctx.seconds, ctx.traffic)
    state["seen"] = seen
    out = summarize(state["requests"], seen)
    ctx.log(f"serve: {out['attempted']} requests, {out['failed']} failed, "
            f"{out['tokens']} tokens, queue {out['queue_third']} at a third "
            f"and {out['queue_end']} at the end, hop at "
            f"{seen['t_begin']} s, swapped at {seen['t_swap']} s, "
            f"{len(seen['steps'])} steps, stall {out['stall_ms']:.1f} ms, "
            f"generator at most {out['late_ms']:.1f} ms late")
    return {k: v for k, v in out.items() if k not in ("ttft_ms", "itl_ms")}


# ---------------------------------------------------------------------------
# The check
# ---------------------------------------------------------------------------
def sample(seen: Dict, k: int, seed: int) -> List[int]:
    """Indices of ``k`` finished requests: the one with most served tokens,
    the longest of those served across the swap, and the rest drawn from
    the seed."""
    done = [j for j, s in enumerate(seen["subs"])
            if s is not None and s.status == "done"]
    if not done:
        return []
    size = lambda j: len(seen["subs"][j].tokens)             # noqa: E731
    pick = [max(done, key=size)]
    across = [j for j in done if j in seen["swap_len"]
              and 0 < seen["swap_len"][j] < size(j) and j not in pick]
    if across:
        pick.append(max(across, key=size))
    rest = [j for j in done if j not in pick]
    rng = np.random.RandomState(seed)
    more = rng.choice(rest, size=min(k - len(pick), len(rest)),
                      replace=False) if rest else []
    return pick + [int(j) for j in more]


def served(seen: Dict, j: int):
    """(prompt, tokens, number of tokens served before the swap)."""
    sub = seen["subs"][j]
    toks = list(sub.tokens)
    if seen["t_swap"] is None:
        before = len(toks)
    elif j in seen["swap_len"]:
        before = seen["swap_len"][j]
    else:
        # finished before the swap, or admitted after it
        before = len(toks) if seen["stamps"][j][-1] <= seen["t_swap"] else 0
    return list(sub.prompt), toks, before


def logit_gaps(models, cases, pr: R.Precision = R.REF,
               pick: R.Precision = None) -> List[float]:
    """For every served token of every case: the reference's best logit at
    its position minus the logit of the served token (or, with ``pick``,
    of the token that precision ``pick`` puts first)."""
    gaps = []
    fwd = {name: jax.jit(lambda p, t, m=m: R.logits(p, m, t, pr))
           for name, (_, m) in models.items()}
    alt = ({name: jax.jit(lambda p, t, m=m: R.logits(p, m, t, pick))
            for name, (_, m) in models.items()} if pick else None)
    for prompt, toks, before in cases:
        # one shape for every case: causal attention leaves the positions
        # before the padding as they are
        pad = models["src"][1]["max_seq"]
        seq = np.zeros((1, pad), np.int32)
        seq[0, :len(prompt) + len(toks) - 1] = prompt + toks[:-1]
        for name, lo, hi in (("src", 0, before), ("dst", before, len(toks))):
            if hi <= lo:
                continue
            params = models[name][0]
            lg = np.asarray(fwd[name](params, jnp.asarray(seq))[0],
                            np.float32)
            pos = np.arange(len(prompt) - 1 + lo, len(prompt) - 1 + hi)
            best = lg[pos].max(-1)
            if alt is None:
                chosen = np.asarray(toks[lo:hi])
            else:
                chosen = np.asarray(alt[name](params, jnp.asarray(seq))[0]
                                    )[pos].argmax(-1)
            gaps.extend((best - lg[pos, chosen]).tolist())
    return gaps


def reference_models(ctx):
    cfg = ctx.config
    params, op = make_inputs(ctx)
    big = jax.jit(lambda o, p: R.grow(o, p, cfg["src"]))(op, params)
    return {"src": (params, cfg["src"]), "dst": (big, cfg["dst"])}


def _cases(ctx, state):
    seen = state.pop("seen")
    state.clear()
    picks = sample(seen, ctx.traffic["check_requests"],
                   ctx.np_seed(SALT_SAMPLE))
    cases = [served(seen, j) for j in picks]
    ctx.log(f"check: {len(cases)} requests, "
            f"{sum(len(c[1]) for c in cases)} served tokens replayed, "
            f"{sum(1 for c in cases if 0 < c[2] < len(c[1]))} across the "
            f"swap")
    return cases


def _worst(gaps: List[float]) -> Dict[str, float]:
    return {"served_logit_gap": max(gaps) if gaps else float("inf")}


def readings(ctx, state, control: bool = True
             ) -> Dict[str, Dict[str, float]]:
    """The widest gap of the program's served tokens and, with
    ``control``, of the tokens the control (the reference in 8-bit floats)
    puts first at the same positions."""
    cases = _cases(ctx, state)
    models = reference_models(ctx)
    out = {"program": _worst(logit_gaps(models, cases))}
    if control:
        out["control"] = _worst(logit_gaps(models, cases, pick=R.CONTROL))
    return out


def check(ctx, state):
    cases = _cases(ctx, state)
    return ctx.checks(_worst(logit_gaps(reference_models(ctx), cases)))
