"""Round-5 NaN repro + root-cause instrumentation (VERDICT Weak #1).

Recipe from the verdict: synth_codes(138000, 27000, 20M,
seed=2124234134) -> prepare_ratings(device=True) -> train_explicit(
rank=10, iterations=5, lambda_=0.01, seed=11) -> max|U|=inf on hybrid.

Phase 1: reproduce, iteration by iteration (segmented warm-start).
Phase 2: at the last finite state, build the hybrid user-side Gram and
the exact csrb Gram, diff them, and run the Gauss-Jordan sweep with
pivot tracking to see whether any Schur pivot goes <= 0.
"""
import os, sys, time
import numpy as np

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      os.path.join(_REPO, ".jax_cache"))
import jax
import jax.numpy as jnp
from jax import lax

sys.path.insert(0, _REPO)
from predictionio_tpu.ops import als


def synth_codes(n_users: int, n_items: int, nnz: int, seed: int):
    """The recipe's ratings: zipf-ish popularity for items, log-normal
    activity for users, half-star ratings, by inverse-CDF sampling."""
    rng = np.random.default_rng(seed)
    user_w = rng.lognormal(0.0, 1.2, n_users)
    item_w = 1.0 / np.arange(1, n_items + 1) ** 0.8
    u_cdf = np.cumsum(user_w / user_w.sum())
    i_cdf = np.cumsum(item_w / item_w.sum())
    u = np.searchsorted(u_cdf, rng.random(nnz)).astype(np.int32)
    i = np.searchsorted(i_cdf, rng.random(nnz)).astype(np.int32)
    np.clip(u, 0, n_users - 1, out=u)
    np.clip(i, 0, n_items - 1, out=i)
    r = np.clip(np.round(rng.normal(3.5, 1.1, nnz) * 2) / 2, 0.5, 5.0
                ).astype(np.float32)
    return u, i, r


N_U, N_I, NNZ = 138_000, 27_000, 20_000_000
SEED_DATA, SEED_F = 2124234134, 11
RANK, LAM = 10, 0.01

print("== synth + prepare", flush=True)
u, i, r = synth_codes(N_U, N_I, NNZ, SEED_DATA)
t0 = time.perf_counter()
data = als.prepare_ratings(u, i, r, N_U, N_I, device=True)
print(f"prep {time.perf_counter()-t0:.1f}s", flush=True)

U, V = als._seed_factors(SEED_F, N_U, N_I, RANK)

IMPLICIT = os.environ.get("REPRO_IMPLICIT") == "1"


def train_rmse(kernel):
    Uk, Vk = als._seed_factors(SEED_F, N_U, N_I, RANK)
    states = []
    for it in range(1, 11):
        t0 = time.perf_counter()
        if IMPLICIT:
            Uk, Vk = als.train_implicit(data, rank=RANK, iterations=1,
                                        lambda_=LAM, alpha=1.0,
                                        u0=Uk, v0=Vk, kernel=kernel)
        else:
            Uk, Vk = als.train_explicit(data, rank=RANK, iterations=1,
                                        lambda_=LAM, u0=Uk, v0=Vk,
                                        kernel=kernel)
        Uh = np.asarray(Uk); Vh = np.asarray(Vk)
        maxu = float(np.max(np.abs(Uh))); maxv = float(np.max(np.abs(Vh)))
        nan_u = int(np.sum(~np.isfinite(Uh).all(axis=1)))
        nan_v = int(np.sum(~np.isfinite(Vh).all(axis=1)))
        print(f"[{kernel}] iter {it}: max|U|={maxu:.4g} max|V|={maxv:.4g} "
              f"badU={nan_u} badV={nan_v}  ({time.perf_counter()-t0:.1f}s)",
              flush=True)
        states.append((Uh.copy(), Vh.copy()))
        if nan_u or nan_v or not np.isfinite(maxu):
            break
    bu = data.by_user
    mask = (bu.self_idx < N_U).astype(np.float32)
    e = float(als.rmse(Uk, Vk, bu.self_idx, bu.other_idx, bu.rating,
                       jnp.asarray(mask)))
    print(f"[{kernel}] train RMSE after 10 iters: {e:.6f}", flush=True)
    return states, e

kernel = os.environ.get("REPRO_KERNEL", "hybrid")
if kernel == "both":
    _, e_h = train_rmse("hybrid")
    _, e_c = train_rmse("csrb")
    rel = abs(e_h - e_c) / e_c
    print(f"RMSE parity: hybrid={e_h:.6f} csrb={e_c:.6f} rel={rel:.5f}",
          flush=True)
    sys.exit(0)
states, _ = train_rmse(kernel)

if os.environ.get("REPRO_PHASE2") != "1":
    sys.exit(0)
if IMPLICIT:
    # phase 2 builds the EXPLICIT half-step operator (presence-weighted
    # Gram, no YtY term); running it on implicit-trained factors would
    # report errors for a kernel configuration production never runs
    print("phase 2 analysis supports explicit mode only "
          "(REPRO_IMPLICIT=1 set); stopping after phase 1", flush=True)
    sys.exit(0)

# ---- Phase 2: last finite state -> Gram comparison -------------------
last_ok = None
for k, (Uh, Vh) in enumerate(states):
    if np.isfinite(Uh).all() and np.isfinite(Vh).all():
        last_ok = k
if last_ok is None:
    # even iteration 1 blew up: analyse from the seed factors
    print("== phase 2: no finite iteration; analysing from seed factors",
          flush=True)
    Uh, Vh = map(np.asarray, als._seed_factors(SEED_F, N_U, N_I, RANK))
else:
    print(f"== phase 2: analysing user half-step from state after iter "
          f"{last_ok+1}", flush=True)
    Uh, Vh = states[last_ok]
V0 = jnp.asarray(Vh)

# exact user-side Gram via csrb kernel
b = als._CSRB_B
bu = data.by_user
u_oi, u_rat, u_pres, u_seg, u_chunk = als._csrb_side(bu, b, 1 << 18, data.nnz)
A_ref, rhs_ref = als.gram_rhs_csrb(V0, u_oi, u_pres, u_rat, u_seg,
                                   N_U, b, u_chunk)
A_ref = np.asarray(A_ref); rhs_ref = np.asarray(rhs_ref)

# hybrid user-side Gram
K = int(os.environ.get("PIO_ALS_HOT_K", als._HOT_K))
hy = als._hybrid_prepare(data, K, False, 0.0, b, 1 << 18)
rr = RANK
X = als._expand_X(V0, rr, jnp.float32)
# f32 into the dense kernel — it splits hi/lo internally; a pre-cast
# would zero the lo correction and analyse a kernel production doesn't run
# hot_ids come from lax.top_k over item counts: in [0, n_items) by
# construction, and the production kernel is mirrored unchanged here
X_hot = jnp.take(X, hy.hot_ids, axis=0)  # pio-lint: allow=gather-clip
AB = als._dense_hot_user(hy.D, X_hot, hy.K, rr)
AB = AB + als._gram_tail(X, hy.u_tail, N_U, b, hy.u_chunk, False, 0.0, rr)
A_hy = np.asarray(AB[:, :rr*rr].reshape(N_U, rr, rr))
rhs_hy = np.asarray(AB[:, rr*rr:rr*rr+rr])

dA = np.abs(A_hy - A_ref).max(axis=(1, 2))
scale = np.abs(A_ref).max(axis=(1, 2)) + 1e-9
counts = np.asarray(bu.counts)
reg = LAM * np.maximum(counts, 1)
print(f"gram abs err: max={dA.max():.4g} p99={np.percentile(dA,99):.4g}")
print(f"gram rel err: max={(dA/scale).max():.4g}")
print(f"rows where gram err > ridge: {(dA > reg).sum()}")

# eigenvalue check on worst rows
worst = np.argsort(-(dA / np.maximum(reg, 1e-9)))[:10]
for w in worst:
    Areg = A_hy[w] + reg[w] * np.eye(rr)
    ev = np.linalg.eigvalsh(0.5 * (Areg + Areg.T))
    evr = np.linalg.eigvalsh(A_ref[w] + reg[w] * np.eye(rr))
    print(f"row {w}: count={counts[w]} ridge={reg[w]:.3g} "
          f"min-eig hybrid={ev[0]:.4g} csrb={evr[0]:.4g} errA={dA[w]:.4g}")

# Schur pivot tracking through the unpivoted sweep on the hybrid Gram
M = np.concatenate([A_hy + reg[:, None, None] * np.eye(rr)[None],
                    rhs_hy[..., None]], axis=2)
min_piv = np.full(N_U, np.inf)
for k in range(rr):
    den = M[:, k, k].copy()
    min_piv = np.minimum(min_piv, den)
    piv = M[:, k:k+1, :] / den[:, None, None]
    M = M - M[:, :, k:k+1] * piv
    M[:, k, :] = piv[:, 0, :]
neg = (min_piv <= 0).sum()
tiny = (min_piv < 0.1 * reg).sum()
print(f"rows with Schur pivot <= 0: {neg}; < 0.1*ridge: {tiny}")
sol_max = np.abs(M[:, :, rr]).max()
print(f"max |solution| from hybrid Gram sweep: {sol_max:.4g}")
