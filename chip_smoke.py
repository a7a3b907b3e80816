"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py              # full size; exit 0 only on a TPU
    JAX_PLATFORMS=cpu python chip_smoke.py --rehearse   # tiny, CPU, not a pass

One process, three phases, through the entry points a user calls:

  kernels  every pallas_call in ops/pallas/ compiled by Mosaic, forward and
           backward, against its jnp / lax.scan oracle at the shapes the
           repo's own models use (the Mamba-2 decode kernel at granite's
           cell, its new state bit for bit the oracle's);
  serve    a ServingServer over the session `serve --demo` builds, a
           ServingClient over TCP, eight mixed-length greedy requests plus
           one streamed — at the CLI's demo geometry and at one lane-aligned
           geometry (16 heads x 128, page size 16) — with the Mosaic call in
           the compiled decode step and tokens checked against the same
           session under PADDLE_TPU_PALLAS=0 on the same chip; then one
           request through a tiny LoopedLM (layers run four times, bfloat16
           pool, the kernel's layer a traced scalar), checked the same way;
           then requests through a small HybridMoELM (Mamba-2 and grouped-
           head attention layers, routed experts of which half are held,
           recurrent state beside the pages), checked the same way;
  train    ResNet-50 at full width (224x224, 1000 classes, bf16 policy,
           batch 256) through SGDTrainer.train over a DataParallel mesh, a
           few single-step dispatches and a few K-step ones, cost finite and
           falling on a repeated batch.

No phase's exception is caught: a phase that fails ends the run with a
non-zero exit code and no result line. Weights and data are random, made
from a seed; nothing is read from the network. Times printed here are
information for the builder, not metrics. On success on a TPU the last line
of stdout is {"ok": true, "device": {...}} as jax reports the device.

With more than one chip the same script shards the batch over all of them
(and checks the replicas agree bitwise) and adds a tp=<count> serving leg
whose tokens must equal the one-chip session's.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import sys
import time

# Tolerances, stated: the oracles run true-f32 dots (Precision.HIGHEST); the
# kernels' f32 dots go through the MXU as Mosaic lowers them. A wrong kernel
# is off by O(1); this bounds MXU rounding over a 100-step recurrence.
KERNEL_TOL = 2e-2
# Where the kernel and the PADDLE_TPU_PALLAS=0 session first emit different
# tokens, the two candidates' reference logits must be this close (a near
# tie flipped by attention rounding), else the kernel is wrong.
LOGIT_TIE_TOL = 5e-2
# The same for the bfloat16 LoopedLM leg, whose logits (std about 16) carry
# bfloat16's 2**-8 of their size through every layer.
LOOPED_TIE_TOL = 0.5
# The bfloat16 HybridMoELM leg: its logits' std is about 0.15 (a tied head
# at 1/sqrt(vocab), over logits_scaling), and a near tie in a router flips
# an expert as well as a token.
HYBRID_TIE_TOL = 0.05
# The Mamba-2 decode kernel's y against its oracle's, over the largest |y|:
# the same float32 products summed in another order (the new state must be
# the oracle's bit for bit)
SSM_Y_TOL = 1e-5
# The expert block with the grouped-matmul kernel against ragged-dot's, over
# its largest output: the same bfloat16 products accumulated in float32 (the
# interpreter's dot on the CPU sums in another order)
MOE_TOL = 1e-2

FULL = dict(
    gru=[(50, 128, 512)],
    lstm=[(100, 64, 256), (100, 64, 1280)],
    attn=[(16, 128, 128, 128)],
    # (slots, heads, head_dim, page_size, pages_per_seq)
    paged=[(8, 2, 16, 16, 8), (16, 16, 128, 16, 8)],
    # (slots, Mamba layers, heads, head_dim, state): granite's cell
    ssm=[(64, 9, 128, 64, 128)],
    # (tokens, top_k, expert layers, held, routed, d_model, expert width):
    # the decode step's expert block at trinity_mini's and granite's cells,
    # and trinity_mini's 2048-token chunk, whose rows stream in tiles (two
    # expert layers of the stack, the second run)
    moe=[(64, 8, 2, 128, 128, 2048, 1024), (64, 10, 2, 36, 72, 4096, 768),
         (2048, 8, 2, 128, 128, 2048, 1024)],
    serve=[
        ["--demo"],
        ["--demo", "--d_model=2048", "--n_heads=16", "--n_layers=2",
         "--vocab=50304", "--page_size=16", "--max_slots=16",
         "--prefill_buckets=16,32", "--max_new_limit=32"],
    ],
    image=224, batch=256, classes=1000, single_steps=5, k=2, k_dispatches=2,
)
REHEARSAL = dict(
    gru=[(5, 8, 128)],
    lstm=[(6, 8, 128)],
    attn=[(2, 16, 16, 32)],
    paged=[(4, 2, 16, 8, 3), (4, 2, 128, 8, 3)],
    ssm=[(2, 3, 8, 64, 128)],
    moe=[(8, 2, 2, 4, 8, 128, 64), (600, 2, 2, 4, 8, 128, 64)],
    serve=[
        ["--demo", "--prefill_buckets=16,32", "--max_new_limit=16"],
        ["--demo", "--d_model=512", "--n_heads=4", "--n_layers=1",
         "--vocab=512", "--page_size=16", "--prefill_buckets=16,32",
         "--max_new_limit=16"],
    ],
    image=32, batch=8, classes=1000, single_steps=3, k=2, k_dispatches=1,
)


def say(msg: str) -> None:
    print(msg, flush=True)


@contextlib.contextmanager
def pallas_flag(value: str):
    """PADDLE_TPU_PALLAS for the programs traced inside: the dispatch
    policy reads it at trace time."""
    prev = os.environ.get("PADDLE_TPU_PALLAS")
    os.environ["PADDLE_TPU_PALLAS"] = value
    try:
        yield
    finally:
        if prev is None:
            del os.environ["PADDLE_TPU_PALLAS"]
        else:
            os.environ["PADDLE_TPU_PALLAS"] = prev


def ms_per_call(fn, *args, n: int = 5) -> float:
    import jax

    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t0) / n


# -- phase: kernels -----------------------------------------------------------


def check_kernel(name, fn, args, kernel_flag, on_chip):
    """`fn(*args) -> (value, grads)` traced twice: with the kernel dispatched
    and with PADDLE_TPU_PALLAS=0 (the oracle, at true-f32 dot precision)."""
    import jax
    import numpy as np

    with pallas_flag(kernel_flag):
        kernel = jax.jit(fn).lower(*args).compile()
    assert ("tpu_custom_call" in kernel.as_text()) == on_chip, (
        f"{name}: a Mosaic custom call is expected in the compiled program "
        "on the chip, and only there"
    )
    with pallas_flag("0"), jax.default_matmul_precision("highest"):
        oracle = jax.jit(fn).lower(*args).compile()
    got, want = kernel(*args), oracle(*args)
    t_kernel, t_oracle = ms_per_call(kernel, *args), ms_per_call(oracle, *args)
    worst = 0.0
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        assert g.shape == w.shape and np.isfinite(g).all(), name
        err = np.max(np.abs(g - w)) / max(1.0, np.max(np.abs(w)))
        worst = max(worst, float(err))
    say(f"  {name}: max err vs oracle {worst:.2e} (tol {KERNEL_TOL:.0e}); "
        f"info: {t_kernel:.2f} ms kernel, {t_oracle:.2f} ms oracle "
        "at true-f32 dots")
    assert worst <= KERNEL_TOL, f"{name}: {worst} > {KERNEL_TOL}"


def phase_kernels(size, on_chip: bool) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops import attention, pallas, rnn
    from paddle_tpu.serving.model import LMConfig, ServableLM

    flag = "auto" if on_chip else "interpret"
    with pallas_flag(flag):
        assert pallas.enabled()
        assert pallas.interpret_mode() == (not on_chip)
    rs = np.random.RandomState(0)

    def arr(*shape, scale=1.0):
        return jnp.asarray(rs.randn(*shape) * scale, jnp.float32)

    def ragged_mask(b, t):
        lens = rs.randint(max(1, t // 2), t + 1, b)
        lens[0] = t
        return jnp.asarray(np.arange(t)[None, :] < lens[:, None], jnp.float32)

    for t, b, h in size["lstm"]:
        w_out, w_last = arr(b, t, h), arr(b, h)

        def lstm(proj, mask, w_hh, bias):
            def loss(proj, w_hh, bias):
                params = rnn.LstmParams(w_hh, bias)
                hs, hl, cl = rnn.lstm_scan(proj, mask, params)
                return jnp.sum(hs * w_out) + jnp.sum((hl + cl) * w_last), hs
            (_, hs), grads = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(
                proj, w_hh, bias)
            return hs, grads

        check_kernel(
            f"lstm T{t} B{b} H{h}", lstm,
            (arr(b, t, 4 * h), ragged_mask(b, t),
             arr(h, 4 * h, scale=h ** -0.5), arr(4 * h, scale=0.1)),
            flag, on_chip,
        )

    for t, b, h in size["gru"]:
        w_out, w_last = arr(b, t, h), arr(b, h)

        def gru(proj, mask, w_hzr, w_hc, bias):
            def loss(proj, w_hzr, w_hc, bias):
                params = rnn.GruParams(w_hzr, w_hc, bias)
                hs, hl = rnn.gru_scan(proj, mask, params)
                return jnp.sum(hs * w_out) + jnp.sum(hl * w_last), hs
            (_, hs), grads = jax.value_and_grad(loss, (0, 1, 2, 3), has_aux=True)(
                proj, w_hzr, w_hc, bias)
            return hs, grads

        check_kernel(
            f"gru T{t} B{b} H{h}", gru,
            (arr(b, t, 3 * h), ragged_mask(b, t),
             arr(h, 2 * h, scale=h ** -0.5), arr(h, h, scale=h ** -0.5),
             arr(3 * h, scale=0.1)),
            flag, on_chip,
        )

    for b, tq, tk, d in size["attn"]:
        w_out = arr(b, tq, d)

        def attn(q, k, v, mask):
            def loss(q, k, v):
                out = attention.dot_product_attention(q, k, v, mask=mask)
                return jnp.sum(out * w_out), out
            (_, out), grads = jax.value_and_grad(loss, (0, 1, 2), has_aux=True)(
                q, k, v)
            return out, grads

        check_kernel(
            f"attention B{b} Tq{tq} Tk{tk} D{d}", attn,
            (arr(b, tq, d), arr(b, tk, d), arr(b, tk, d),
             ragged_mask(b, tk)[:, None, :]),
            flag, on_chip,
        )

    for s, heads, hd, ps, pmax in size["paged"]:
        kd, n_pages, layers = heads * hd, 1 + s * pmax, 2
        model = ServableLM(
            LMConfig(vocab=8, n_layers=layers, d_model=kd, n_heads=heads)
        )
        # ragged block table: shuffled physical pages, mixed ages, one empty slot
        table = np.zeros((s, pmax), np.int32)
        positions = np.zeros(s, np.int32)
        free = list(rs.permutation(np.arange(1, n_pages)))
        for slot in range(s - 1):
            n = rs.randint(1, pmax + 1)
            table[slot, :n] = [free.pop() for _ in range(n)]
            positions[slot] = rs.randint(0, n * ps)

        def paged(q, k_pages, v_pages, table, positions):
            return model._paged_attention(
                q, k_pages, v_pages, table, positions, layer=1
            )

        pool = (layers, n_pages, ps, kd)
        check_kernel(
            f"paged attention S{s} H{heads}x{hd} PS{ps}", paged,
            (arr(s, kd), arr(*pool), arr(*pool),
             jnp.asarray(table), jnp.asarray(positions)),
            flag, on_chip,
        )

    for shape in size["ssm"]:
        check_ssm_decode(shape, flag, on_chip)

    for shape in size["moe"]:
        check_expert_block(shape, flag, on_chip)


def check_ssm_decode(shape, kernel_flag, on_chip):
    """The Mamba-2 decode kernel against `mamba2.ssm_step` on the layer's
    slice (HybridMoELM._ssm_decode's two paths), the layer traced, a lane
    inactive and some heads at dt = 0: the new stack bit for bit, y within
    SSM_Y_TOL of the largest |y|."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.serving.hybrid_moe_lm import HybridMoELM

    s, m, h, p, n = shape
    ks = jax.random.split(jax.random.PRNGKey(38), 6)
    active = jnp.arange(s) % 5 != 1
    dt = jax.nn.softplus(jax.random.normal(ks[2], (s, h)) - 2.0)
    args = (
        jax.random.normal(ks[0], (s, m, h, p, n), jnp.float32),
        jnp.asarray(m - 1, jnp.int32),
        jax.random.normal(ks[1], (s, h, p)).astype(jnp.bfloat16),
        jnp.where(active[:, None], dt, 0.0).at[:, ::7].set(0.0),
        -jax.random.uniform(ks[3], (h,), minval=1.0, maxval=16.0),
        jax.random.normal(ks[4], (s, n)).astype(jnp.bfloat16),
        jax.random.normal(ks[5], (s, n)).astype(jnp.bfloat16),
        active,
    )

    def compiled(flag):  # a fresh function a flag: jit's cache is the function's
        with pallas_flag(flag):
            return jax.jit(lambda *a: HybridMoELM._ssm_decode(*a)).lower(*args).compile()

    kernel, oracle = compiled(kernel_flag), compiled("0")
    assert ("tpu_custom_call" in kernel.as_text()) == on_chip, (
        "ssm_decode: a Mosaic custom call is expected on the chip, and only there")
    (y_got, got), (y_want, want) = kernel(*args), oracle(*args)
    t_kernel, t_oracle = ms_per_call(kernel, *args), ms_per_call(oracle, *args)
    differ = int(np.sum(np.asarray(got) != np.asarray(want)))
    y_err = float(jnp.max(jnp.abs(y_got - y_want)) / jnp.max(jnp.abs(y_want)))
    say(f"  ssm_decode S{s} M{m} H{h}x{p} N{n}: state elements not bit for bit "
        f"the oracle's {differ}, y err {y_err:.2e} (tol {SSM_Y_TOL:.0e}); info: "
        f"{t_kernel:.2f} ms kernel, {t_oracle:.2f} ms oracle, a call with its "
        "copy of the stack")
    assert differ == 0 and y_err <= SSM_Y_TOL, (differ, y_err)


def check_expert_block(shape, kernel_flag, on_chip):
    """The routed expert block (serving/moe.py) with its grouped products
    the Pallas kernel's (ops/pallas/grouped_matmul.py) against ragged-dot's,
    the layer traced: the block's output within MOE_TOL of its largest
    entry (bit for bit on the chip: PERF.md, section 6)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.serving import moe

    t, k, layers, held, routed, d, f = shape
    ks = jax.random.split(jax.random.PRNGKey(44), 4)
    args = (
        jax.random.normal(ks[0], (t, d), jnp.bfloat16), jnp.arange(t) % 7 != 3,
        jax.random.normal(ks[1], (d, routed), jnp.bfloat16),
        jax.random.normal(ks[2], (layers, held, d, 2 * f), jnp.bfloat16),
        jax.random.normal(ks[3], (layers, held, f, d), jnp.bfloat16),
        jnp.asarray(layers - 1, jnp.int32),
    )
    local_of = np.minimum(np.arange(routed), held).astype(np.int32)

    def compiled(flag):  # a fresh function a flag: jit's cache is the function's
        with pallas_flag(flag):
            return jax.jit(lambda *a: moe.expert_block(
                *a, top_k=k, local_of=local_of, dtype=jnp.bfloat16)[0]).lower(*args).compile()

    kernel, oracle = compiled(kernel_flag), compiled("0")
    assert ("tpu_custom_call" in kernel.as_text()) == on_chip, (
        "grouped_matmul: a Mosaic custom call is expected on the chip, and only there")
    got, want = kernel(*args).astype(jnp.float32), oracle(*args).astype(jnp.float32)
    t_kernel, t_oracle = ms_per_call(kernel, *args), ms_per_call(oracle, *args)
    differ = int(np.sum(np.asarray(got) != np.asarray(want)))
    err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
    say(f"  expert block T{t} k{k} E{held}/{routed} D{d} F{f}: outputs not bit for bit "
        f"ragged-dot's {differ}, err {err:.2e} (tol {MOE_TOL:.0e}); info: "
        f"{t_kernel:.2f} ms kernel, {t_oracle:.2f} ms ragged-dot, a block")
    assert err <= MOE_TOL, (differ, err)


# -- phase: serve -------------------------------------------------------------


def serve_args(argv):
    from paddle_tpu import cli

    parser = argparse.ArgumentParser()
    cli._serve_args(parser)
    return parser.parse_args(argv)


def first_divergence_is_a_tie(session, prompt, got, want, tol=None) -> bool:
    """Teacher-forced on the common prefix, are the two candidate tokens'
    reference logits (the full-context forward the repo's tests compare
    against) within `tol` (LOGIT_TIE_TOL)? A list that simply stopped stands
    for the end-of-sequence token there."""
    tol = LOGIT_TIE_TOL if tol is None else tol
    import jax
    import jax.numpy as jnp

    at = next(
        (i for i, (a, b) in enumerate(zip(got, want)) if a != b),
        min(len(got), len(want)),
    )
    a, b = (t[at] if at < len(t) else session.cfg.eos_id for t in (got, want))
    ctx = jnp.asarray([prompt + got[:at]], jnp.int32)
    with jax.default_matmul_precision("highest"):
        logits = session.model.forward_logits(session.params, ctx)[0, -1]
    gap = abs(float(logits[a]) - float(logits[b]))
    say(f"    tokens diverge at {at}: logit gap {gap:.3e} "
        f"(tie tol {tol:.0e})")
    return gap <= tol


def serve_one(argv, kernel_flag: str, on_chip: bool, max_new: int):
    """Serve the mixed-length prompts through the server and a TCP client;
    returns (tokens, prompts, session)."""
    from paddle_tpu import cli
    from paddle_tpu.core import stats
    from paddle_tpu.serving.server import ServingClient, ServingServer
    from paddle_tpu.serving.workload import make_prompts

    def compiles():  # every XLA compile asks the persistent cache first
        return stats.RECOMPILES.cache_hits + stats.RECOMPILES.cache_misses

    args = serve_args(argv)
    with pallas_flag(kernel_flag):
        session = cli.build_serve_session(args)
        server = ServingServer(
            session=session, host="127.0.0.1", port=0
        ).start()
        try:
            client = ServingClient(server.address)
            prompts = make_prompts(
                8, (3, 5, 8, 11, 16, 19, 27, session.buckets[-1]),
                args.vocab, session.cfg.bos_id, seed=1,
            )
            # warm-up: one request per prefill bucket compiles every executable
            for bucket in session.buckets:
                client.generate(
                    prompts[-1][:bucket], max_new_tokens=2, timeout_s=600.0
                )
            compiles_warm = compiles()
            rids = [client.submit(p, max_new) for p in prompts]
            streamed = []
            for frame in client.stream(prompts[2], max_new):
                streamed.extend(frame["tokens"])
            tokens = []
            for rid in rids:
                deadline = time.monotonic() + 600.0
                while not (resp := client.poll(rid)).get("done"):
                    assert time.monotonic() < deadline, f"request {rid} not done"
                    time.sleep(0.01)
                assert resp["finish_reason"] in ("length", "eos"), resp
                tokens.append(list(resp["tokens"]))
            served = client.stats()
            client.close()
        finally:
            server.stop()
        assert streamed == tokens[2], "streamed tokens differ from polled ones"
        assert all(1 <= len(t) <= max_new for t in tokens)
        assert served["engine_restarts"] == 0, served
        assert served["decode_shape_signatures"] == 1, served
        assert compiles() == compiles_warm, "a compile after warm-up"
        if kernel_flag != "0":
            mosaic = "tpu_custom_call" in session.decode_step_hlo()
            assert mosaic == on_chip, "Mosaic call expected on the chip only"
    return tokens, prompts, session


def phase_serve(size, on_chip: bool, n_dev: int) -> None:
    flag = "auto" if on_chip else "interpret"
    for argv in size["serve"]:
        args = serve_args(argv)
        max_new = min(24, args.max_new_limit)
        t0 = time.perf_counter()
        got, prompts, session = serve_one(argv, flag, on_chip, max_new)
        want, _, _ = serve_one(argv, "0", on_chip, max_new)
        equal = sum(g == w for g, w in zip(got, want))
        say(f"  {' '.join(argv)}: 8 requests + 1 stream served, "
            f"engine_restarts 0, 0 compiles after warm-up, Mosaic call in the "
            f"decode step: {on_chip}, {equal}/8 token-identical to "
            f"PADDLE_TPU_PALLAS=0; info: {time.perf_counter() - t0:.1f} s")
        for prompt, g, w in zip(prompts, got, want):
            assert g == w or first_divergence_is_a_tie(session, prompt, g, w), (
                f"kernel tokens {g} != oracle tokens {w}"
            )
        if n_dev > 1 and args.n_heads % n_dev == 0 and args.vocab % n_dev == 0:
            tp, _, _ = serve_one(
                argv + [f"--tp={n_dev}"], flag, on_chip, max_new
            )
            assert tp == got, f"tp={n_dev} tokens {tp} != one-chip tokens {got}"
            say(f"  --tp={n_dev}: tokens equal the one-chip session's")
        del session
        gc.collect()
    serve_looped(on_chip)
    serve_hybrid(on_chip)


def serve_hybrid(on_chip: bool) -> None:
    """The third served architecture: three requests (one admitted into a
    slot another left) through ServingSession over a HybridMoELM (m m a m,
    8 Mamba heads of 64 with state 128, 8 query heads over 2 K/V heads of
    128, 8 experts top-3 of which 4 are held, bfloat16): the grouped-head
    kernel, the grouped expert products and the carried recurrent state on
    the chip, against the same session under PADDLE_TPU_PALLAS=0; and the
    session's two refusals."""
    import jax

    from paddle_tpu.serving.hybrid_moe_lm import HybridMoEConfig, HybridMoELM
    from paddle_tpu.serving.session import ServingSession

    model = HybridMoELM(HybridMoEConfig(
        vocab=512, layer_types=("mamba", "mamba", "attention", "mamba"), d_model=256,
        n_heads=8, n_kv_heads=2, head_dim=128, mamba_heads=8, mamba_head_dim=64,
        mamba_state=128, mamba_chunk=16, num_experts_routed=8, experts_held=(0, 1, 2, 3),
        top_k=3, expert_width=128, shared_width=256, embedding_multiplier=1.0,
        attention_multiplier=1.0 / 128, max_len=96, dtype="bfloat16",
    ))
    params = model.init_params(jax.random.PRNGKey(0))
    prompts = [[1, 17, 201, 5, 88, 140, 9, 33, 250, 61, 7], [1, 9, 9, 200, 13],
               [1] + list(range(40, 70))]
    kw = dict(max_slots=2, page_size=16, prefill_buckets=(16, 32), max_new_limit=24)

    def serve(flag):
        with pallas_flag(flag):
            session = ServingSession(model, params, **kw)
            handles = [session.submit(p, 12) for p in prompts]
            session.run_until_idle()
            mosaic = "tpu_custom_call" in session.decode_step_hlo()
        assert session.k_pages.shape[0] == 1 and session.layer_passes == 4
        assert session.decode_shape_signatures() == 1
        counted = session.read_counters()["moe_assignments"]
        assert (counted.sum(1) == 3 * (sum(map(len, prompts)) + 3 * 11)).all(), counted
        return [[int(t) for t in h.tokens] for h in handles], mosaic, session

    t0 = time.perf_counter()
    got, mosaic, session = serve("auto" if on_chip else "interpret")
    assert mosaic == on_chip, "Mosaic call expected on the chip only"
    want, _, _ = serve("0")
    for prompt, g, w in zip(prompts, got, want):
        assert len(g) == 12 and g[0] == w[0], (g, w)
        assert g == w or first_divergence_is_a_tie(session, prompt, g, w, HYBRID_TIE_TOL), (
            f"kernel tokens {g} != oracle tokens {w}")
    for refused in (dict(prefix_cache=True, prefill_chunk=16), dict(speculate_k=2)):
        try:
            ServingSession(model, params, **kw, **refused)
        except ValueError as e:
            assert "recurrence" in str(e)
        else:
            raise AssertionError(f"a session with {refused} was built over a recurrence")
    say(f"  HybridMoELM m m a m, 8 heads over 2 K/V heads, 4 of 8 experts held, bfloat16: "
        f"3 requests served, Mosaic call in the decode step: {on_chip}, "
        f"{sum(g == w for g, w in zip(got, want))}/3 token-identical to PADDLE_TPU_PALLAS=0 "
        f"(the rest tied at the first divergence); info: {time.perf_counter() - t0:.1f} s")


def serve_looped(on_chip: bool) -> None:
    """The second served architecture beside the first: one request through
    ServingSession over a LoopedLM (2 layers run 4 times, 2 heads of 128,
    bfloat16 weights and pool), the kernel taking the cache layer as a
    traced scalar from inside the scan, against the same session under
    PADDLE_TPU_PALLAS=0."""
    import jax

    from paddle_tpu.serving.looped_lm import LoopedLM, LoopedLMConfig
    from paddle_tpu.serving.session import ServingSession

    model = LoopedLM(LoopedLMConfig(
        vocab=512, n_layers=2, d_model=256, n_heads=2, head_dim=128, d_ff=384,
        ut_steps=4, max_len=64, dtype="bfloat16",
    ))
    params = model.init_params(jax.random.PRNGKey(0))
    prompt = [1, 17, 201, 5, 88, 140, 9, 33, 250, 61, 7]

    def serve(flag):
        with pallas_flag(flag):
            session = ServingSession(
                model, params, max_slots=4, page_size=16,
                prefill_buckets=(16,), max_new_limit=24,
            )
            handle = session.submit(prompt, 16)
            session.run_until_idle()
            mosaic = "tpu_custom_call" in session.decode_step_hlo()
        assert str(session.k_pages.dtype) == "bfloat16"
        assert session.k_pages.shape[0] == 8 == session.layer_passes
        assert session.decode_shape_signatures() == 1
        return [int(t) for t in handle.tokens], mosaic, session

    t0 = time.perf_counter()
    got, mosaic, session = serve("auto" if on_chip else "interpret")
    assert mosaic == on_chip, "Mosaic call expected on the chip only"
    want, _, _ = serve("0")
    assert len(got) == 16 and got[0] == want[0], (got, want)
    if got != want:
        # bfloat16: the kernel's recurrence rounds other weights than the
        # oracle's softmax, so a near tie may flip; LOOPED_TIE_TOL bounds it
        assert first_divergence_is_a_tie(session, prompt, got, want, LOOPED_TIE_TOL), (
            f"kernel tokens {got} != oracle tokens {want}"
        )
    say(f"  LoopedLM 2 layers x 4 passes, bfloat16 pool {tuple(session.k_pages.shape)}: "
        f"1 request served, Mosaic call in the decode step: {on_chip}, tokens "
        f"{'equal' if got == want else 'tied at the first divergence'} to "
        f"PADDLE_TPU_PALLAS=0; info: {time.perf_counter() - t0:.1f} s")


# -- phase: train -------------------------------------------------------------


def phase_train(size, on_chip: bool, n_dev: int):
    import jax
    import numpy as np

    from paddle_tpu import models
    from paddle_tpu.core import stats
    from paddle_tpu.nn.graph import reset_name_scope
    from paddle_tpu.optim import SGD
    from paddle_tpu.parallel import DataParallel, make_mesh
    from paddle_tpu.trainer import SGDTrainer
    from paddle_tpu.trainer.events import EndIteration

    hits0, misses0 = stats.RECOMPILES.cache_hits, stats.RECOMPILES.cache_misses
    reset_name_scope()
    _img, _label, _logits, cost = models.resnet50(
        num_classes=size["classes"], image_size=size["image"]
    )
    dp = DataParallel(make_mesh({"data": n_dev}))
    trainer = SGDTrainer(
        cost, SGD(learning_rate=0.01, momentum=0.9), parallel=dp,
        precision="bf16", seed=0,
    )
    rs = np.random.RandomState(0)
    batch = dp.shard_batch({
        "image": rs.randn(
            size["batch"], size["image"], size["image"], 3
        ).astype(np.float32),
        "label": rs.randint(0, size["classes"], size["batch"]),
    })

    costs, stamps = [], [time.perf_counter()]

    def on_event(event):
        if isinstance(event, EndIteration):
            costs.append(float(event.cost))  # the fetch is the barrier
            stamps.append(time.perf_counter())

    def reader(n):
        return lambda: (batch for _ in range(n))

    # warm-up step (compiles) + single-step dispatches
    trainer.train(reader(1 + size["single_steps"]), event_handler=on_event)
    n_single = len(costs)
    # K-step fused dispatches: make_multi_step compiles too
    trainer.train(
        reader(size["k"] * size["k_dispatches"]), event_handler=on_event,
        steps_per_dispatch=size["k"],
    )
    assert len(costs) == n_single + size["k_dispatches"], costs
    assert np.isfinite(costs).all(), costs
    assert costs[-1] < costs[0], f"cost did not fall: {costs}"
    step_s = np.diff(stamps)
    say("  costs " + " ".join(f"{c:.4f}" for c in costs))
    say(f"  info: first dispatch {step_s[0]:.1f} s (compile), later single "
        f"steps {1e3 * np.median(step_s[1:n_single]):.1f} ms each, first "
        f"K={size['k']} dispatch {step_s[n_single]:.1f} s (compile)")
    if n_dev > 1:
        for d in jax.devices() if on_chip else ():  # the CPU reports none
            in_use = d.memory_stats()["bytes_in_use"]
            assert in_use > 0, f"{d} holds nothing"
            say(f"  {d}: {in_use / 2**20:.0f} MiB in use")
        name, leaf = next(iter(sorted(trainer.state["params"].items())))
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        assert len(shards) == n_dev and all(
            s.tobytes() == shards[0].tobytes() for s in shards
        ), f"replicated parameter {name} differs between chips"
        say(f"  replicated parameter {name}: bitwise equal on {n_dev} chips")
    return (stats.RECOMPILES.cache_hits - hits0,
            stats.RECOMPILES.cache_misses - misses0)


# -- main ---------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on the CPU backend (needs "
                         "JAX_PLATFORMS=cpu); checks the script, is not a "
                         "pass, exits 3")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    import jax
    import jaxlib

    import paddle_tpu
    from paddle_tpu.core import stats

    paddle_tpu.init(use_tpu=True, seed=0)
    devices = jax.devices()
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "absent"
    say(f"platform: {device['platform']}  device_kind: {device['kind']}  "
        f"devices: {device['count']}")
    say(f"jax {jax.__version__}  jaxlib {jaxlib.__version__}  "
        f"libtpu {libtpu_version}")
    say(f"compile cache: {jax.config.jax_compilation_cache_dir}")
    if args.rehearse:
        if device["platform"] != "cpu":
            say("chip_smoke: --rehearse is the CPU rehearsal; run it under "
                "JAX_PLATFORMS=cpu")
            return 2
    elif device["platform"] != "tpu":
        print(f"chip_smoke: jax's default backend is {device['platform']!r} "
              f"({device['kind']}), not a TPU", file=sys.stderr)
        return 2
    on_chip = not args.rehearse
    size = FULL if on_chip else REHEARSAL

    def run(phase, *phase_args):
        name = phase.__name__.removeprefix("phase_")
        say(f"[{name}]")
        t0 = time.perf_counter()
        result = phase(size, on_chip, *phase_args)
        say(f"[{name}] ok; info: {time.perf_counter() - t0:.1f} s")
        return result

    run(phase_kernels)
    run(phase_serve, len(devices))
    train_hits, train_misses = run(phase_train, len(devices))
    say(f"compile cache hits/misses: train phase {train_hits}/{train_misses}, "
        f"whole run {stats.RECOMPILES.cache_hits}/{stats.RECOMPILES.cache_misses}")
    say(f"info: wall time {time.perf_counter() - t_start:.1f} s")
    if args.rehearse:
        say("rehearsal complete on platform cpu: not a pass")
        return 3
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
