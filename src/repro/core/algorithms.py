"""FL algorithms (paper §5.1): FedAvg, FedProx, FedNova, Mime (stateless);
SCAFFOLD, FedDyn (stateful clients).

Each algorithm declares OP types for everything it communicates (paper §3.2)
and plugs into the Parrot round engine unchanged — the engine neither knows
nor cares which algorithm runs; it only schedules tasks, folds OP-typed
payloads and moves client state through the state manager.

The algorithms are generic over the model: they receive a ``grad_fn(params,
batch) -> (loss, grads)`` and operate on parameter pytrees, so the same code
trains a logistic regression in the unit tests, a CNN at paper scale in the
benchmarks, and a reduced LM in the integration tests.  A grad_fn may
return ``((loss, stats), grads)`` instead (``launch/train.build_grad_fn``
does): ``stats`` are the model's step counters, which the compiled steps
sum over the real local steps (``core/client_step.py``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.aggregation import ClientResult, Op

Pytree = Any
GradFn = Callable[[Pytree, Any], Tuple[jnp.ndarray, Pytree]]


def rounded(t):
    """``t`` itself (``copysign(t, t)`` is ``t``, bit for bit), in a form
    the compiler cannot see through: a product passed through it keeps its
    own rounding before the add that consumes it, as in eager execution,
    where XLA's CPU backend would otherwise contract the multiply and the
    add of one fused loop into a single fused multiply-add and round once.
    So the compiled server step computes what the eager reference
    computes, bit for bit."""
    return jnp.copysign(t, t)


def tree_add(a, b, scale=1.0):
    """``a + scale * b`` in ``a``'s dtype: the server folds an fp32
    aggregate into the model, and promoting a bf16 model to fp32 there would
    make every later round recompile the client step for fp32 and double
    its memory.  A literal unit scale forms no product."""
    unit = isinstance(scale, (int, float)) and scale == 1
    return jax.tree.map(
        lambda x, y: (x + (y if unit else rounded(scale * y))).astype(x.dtype),
        a, b)


def tree_sub(a, b):
    return jax.tree.map(lambda x, y: x - y, a, b)


def tree_scale(a, s):
    return jax.tree.map(lambda x: rounded(x * s), a)


def tree_zeros_like(a):
    return jax.tree.map(jnp.zeros_like, a)


@dataclass
class ClientData:
    """One client's local data: an iterable of batches (repeated E epochs by
    the algorithm) plus its sample count N_m (the scheduling signal)."""
    batches: List[Any]
    n_samples: int


class FLAlgorithm:
    name: str = "base"
    stateful: bool = False

    def __init__(self, grad_fn: GradFn, lr: float, local_epochs: int = 1,
                 server_lr: float = 1.0, **kw):
        self.grad_fn = grad_fn
        self.lr = lr
        self.local_epochs = local_epochs
        self.server_lr = server_lr

    # --- interface -------------------------------------------------------
    def ops(self) -> Dict[str, Op]:
        raise NotImplementedError

    def broadcast_payload(self, params: Pytree, server_state: Dict) -> Dict:
        """Θ^r — what the server sends to every executor each round."""
        return {"params": params}

    def client_init_state(self, params: Pytree) -> Optional[Pytree]:
        return None

    def client_update(self, payload: Dict, data: ClientData,
                      state: Optional[Pytree]
                      ) -> Tuple[ClientResult, Optional[Pytree]]:
        raise NotImplementedError

    def server_init(self, params: Pytree) -> Dict:
        return {}

    def server_scalars(self, n_selected: int,
                       n_total_clients: int) -> Dict[str, float]:
        """The round's numbers ``server_update`` reads besides the aggregate,
        computed on the host in double precision.  The compiled server step
        takes them as traced scalars, so one executable serves every
        round."""
        return {}

    def server_update(self, params: Pytree, agg: Dict, server_state: Dict,
                      scalars: Dict[str, Any]) -> Tuple[Pytree, Dict]:
        """(new params, new server state) from the round's aggregate
        ``{entry: pytree}`` and ``server_scalars``.  Pure jnp and elementwise
        over leaves: the server traces it into one compiled step
        (``core/round.py``) that hands it every leaf flattened to 1-D."""
        raise NotImplementedError

    # --- shared local-SGD loop --------------------------------------------
    def _local_sgd(self, params0: Pytree, data: ClientData,
                   grad_hook: Optional[Callable] = None) -> Tuple[Pytree, int]:
        """Plain local SGD with an optional per-step gradient correction.
        Returns (final params, number of local steps tau_m)."""
        w = params0
        tau = 0
        for _ in range(self.local_epochs):
            for batch in data.batches:
                _, g = self.grad_fn(w, batch)
                if grad_hook is not None:
                    g = grad_hook(w, g)
                w = tree_add(w, g, -self.lr)
                tau += 1
        return w, tau

    # --- pure per-step form (compiled engine; core/client_step.py) --------
    #
    # Each algorithm re-expresses its local update as a pure
    # ``(carry, batch, mask) -> carry`` function over an explicit carry
    # pytree (params plus whatever the steps read: the FedProx anchor,
    # SCAFFOLD variates, the FedDyn corrector, Mime's frozen momentum).
    # ``mask`` is 1.0 for real steps and 0.0 for the padding steps the
    # engine appends to bucket scan lengths — a masked step multiplies the
    # update by zero, so padding is exact.  The engine rolls ``local_step``
    # into one jitted ``lax.scan`` over all tau = local_epochs x n_batches
    # steps and vmaps it over blocks of clients; ``client_update`` above
    # stays as the eager reference path (used by ``run_flat_reference``).

    def init_carry(self, payload: Dict, state: Optional[Pytree]) -> Pytree:
        return {"w": payload["params"]}

    def step_correction(self, carry: Pytree, g: Pytree) -> Pytree:
        """Per-step gradient correction (the pure analogue of grad_hook)."""
        return g

    def local_step(self, carry: Pytree, batch: Any, mask: jnp.ndarray
                   ) -> Tuple[Pytree, Pytree]:
        """(new carry, the step's stats: the grad_fn's, zero for a padded
        step, ``{}`` where the grad_fn returns a bare loss)."""
        out, g = self.grad_fn(carry["w"], batch)
        with jax.named_scope("opt"):
            g = self.step_correction(carry, g)
            # mask is cast to each leaf's dtype (0/1 are exact in any float
            # dtype): an f32 mask would promote a bf16 carry and break the
            # scan's carry-type invariant
            w = jax.tree.map(
                lambda ww, gg: ww - self.lr * mask.astype(ww.dtype) * gg,
                carry["w"], g)
        stats = out[1] if isinstance(out, tuple) else {}
        return dict(carry, w=w), jax.tree.map(
            lambda v: mask.astype(v.dtype) * v, stats)

    def finalize(self, carry: Pytree, payload: Dict, state: Optional[Pytree],
                 batches: Any, mask: jnp.ndarray
                 ) -> Tuple[Dict[str, Any], Optional[Pytree]]:
        """(result payload, new client state) from the final carry — pure;
        the aggregation weight is applied by the caller."""
        raise NotImplementedError

    def _tau(self, mask: jnp.ndarray) -> jnp.ndarray:
        """Real local-step count tau_m = E x n_batches (mask sums the
        un-padded batches), floored at 1 like the eager ``max(tau, 1)``."""
        return jnp.maximum(self.local_epochs * jnp.sum(mask), 1.0)


# ---------------------------------------------------------------------------
# Stateless algorithms
# ---------------------------------------------------------------------------

class FedAvg(FLAlgorithm):
    name = "fedavg"

    def ops(self):
        return {"delta": Op.WEIGHTED_AVG}

    def client_update(self, payload, data, state):
        w, tau = self._local_sgd(payload["params"], data)
        delta = tree_sub(w, payload["params"])
        return ClientResult({"delta": delta}, self.ops(),
                            weight=float(data.n_samples)), None

    def server_update(self, params, agg, server_state, scalars):
        return tree_add(params, agg["delta"], self.server_lr), server_state

    def finalize(self, carry, payload, state, batches, mask):
        return {"delta": tree_sub(carry["w"], payload["params"])}, None


class FedProx(FedAvg):
    name = "fedprox"

    def __init__(self, *a, mu: float = 0.01, **kw):
        super().__init__(*a, **kw)
        self.mu = mu

    def client_update(self, payload, data, state):
        anchor = payload["params"]

        def hook(w, g):  # g + mu * (w - w_global)
            return jax.tree.map(lambda gg, ww, aa: gg + self.mu * (ww - aa),
                                g, w, anchor)

        w, tau = self._local_sgd(anchor, data, hook)
        delta = tree_sub(w, anchor)
        return ClientResult({"delta": delta}, self.ops(),
                            weight=float(data.n_samples)), None

    def init_carry(self, payload, state):
        return {"w": payload["params"], "anchor": payload["params"]}

    def step_correction(self, carry, g):  # g + mu * (w - w_global)
        return jax.tree.map(lambda gg, ww, aa: gg + self.mu * (ww - aa),
                            g, carry["w"], carry["anchor"])


class FedNova(FLAlgorithm):
    """Normalised averaging (Wang et al., 2020): clients return the
    step-normalised delta plus an aggregation weight tau (the paper's example
    of an extra averaged parameter)."""
    name = "fednova"

    def ops(self):
        return {"norm_delta": Op.WEIGHTED_AVG, "tau": Op.WEIGHTED_AVG}

    def client_update(self, payload, data, state):
        w, tau = self._local_sgd(payload["params"], data)
        delta = tree_sub(w, payload["params"])
        norm_delta = tree_scale(delta, 1.0 / max(tau, 1))
        return ClientResult(
            {"norm_delta": norm_delta, "tau": jnp.float32(tau)},
            self.ops(), weight=float(data.n_samples)), None

    def server_update(self, params, agg, server_state, scalars):
        tau_eff = agg["tau"]
        new = tree_add(params, tree_scale(agg["norm_delta"], tau_eff),
                       self.server_lr)
        return new, server_state

    def finalize(self, carry, payload, state, batches, mask):
        tau = self._tau(mask)     # traced f32: cast back to the leaf dtype
        delta = tree_sub(carry["w"], payload["params"])
        return {"norm_delta": jax.tree.map(
                    lambda d: (d / tau).astype(d.dtype), delta),
                "tau": jnp.asarray(tau, jnp.float32)}, None


class Mime(FLAlgorithm):
    """Mime (Karimireddy et al., 2020a): the server optimizer state (momentum)
    is broadcast and applied — but not updated — during local steps; clients
    additionally return a full-batch gradient at the *global* params, which
    the paper treats as a Special Param (collected, not averaged): comm size
    O(s_e · M_p) cannot be reduced by hierarchical aggregation (§4.2)."""
    name = "mime"

    def __init__(self, *a, beta: float = 0.9, **kw):
        super().__init__(*a, **kw)
        self.beta = beta

    def ops(self):
        return {"delta": Op.WEIGHTED_AVG, "full_grad": Op.COLLECT}

    def broadcast_payload(self, params, server_state):
        return {"params": params, "momentum": server_state["momentum"]}

    def server_init(self, params):
        return {"momentum": tree_zeros_like(params)}

    def client_update(self, payload, data, state):
        mom = payload["momentum"]

        def hook(w, g):  # momentum-corrected step, momentum frozen locally
            return jax.tree.map(
                lambda gg, mm: (1 - self.beta) * gg + self.beta * mm, g, mom)

        w, tau = self._local_sgd(payload["params"], data, hook)
        # full-batch gradient at the global params (server momentum update)
        gs = None
        n = 0
        for batch in data.batches:
            _, g = self.grad_fn(payload["params"], batch)
            gs = g if gs is None else tree_add(gs, g)
            n += 1
        full_grad = tree_scale(gs, 1.0 / max(n, 1))
        delta = tree_sub(w, payload["params"])
        return ClientResult({"delta": delta, "full_grad": full_grad},
                            self.ops(), weight=float(data.n_samples)), None

    def server_update(self, params, agg, server_state, scalars):
        grads = agg["full_grad"]                  # list of (weight, pytree)
        # one stacked (M_p, ...) weighted average per leaf instead of a
        # per-client python loop over every leaf on the server path
        ws = jnp.asarray([w for w, _ in grads], jnp.float32)
        ws = ws / jnp.maximum(jnp.sum(ws), 1e-12)
        gavg = jax.tree.map(
            lambda *leaves: jnp.tensordot(ws, jnp.stack(leaves), axes=1),
            *[g for _, g in grads])
        # cast back to the momentum dtype: the f32 tensordot must not
        # promote a bf16 momentum (next round's scan carry would mismatch)
        mom = jax.tree.map(
            lambda m, g: (rounded(self.beta * m)
                          + rounded((1 - self.beta) * g)).astype(m.dtype),
            server_state["momentum"], gavg)
        new = tree_add(params, agg["delta"], self.server_lr)
        return new, {"momentum": mom}

    def init_carry(self, payload, state):
        return {"w": payload["params"], "momentum": payload["momentum"]}

    def step_correction(self, carry, g):  # momentum frozen locally
        return jax.tree.map(
            lambda gg, mm: (1 - self.beta) * gg + self.beta * mm,
            g, carry["momentum"])

    def finalize(self, carry, payload, state, batches, mask):
        params0 = payload["params"]

        def acc(gs, xs):  # full-batch gradient at the *global* params
            b, m = xs
            _, g = self.grad_fn(params0, b)
            return jax.tree.map(lambda s, gg: s + m.astype(s.dtype) * gg,
                                gs, g), None

        gsum, _ = jax.lax.scan(acc, tree_zeros_like(params0), (batches, mask))
        n = jnp.maximum(jnp.sum(mask), 1.0)
        full_grad = jax.tree.map(lambda s: (s / n).astype(s.dtype), gsum)
        return {"delta": tree_sub(carry["w"], params0),
                "full_grad": full_grad}, None


# ---------------------------------------------------------------------------
# Stateful algorithms
# ---------------------------------------------------------------------------

class Scaffold(FLAlgorithm):
    """SCAFFOLD (Karimireddy et al., 2020b): client control variates c_m are
    client state held by the state manager; the server variate c is broadcast."""
    name = "scaffold"
    stateful = True

    def ops(self):
        return {"delta": Op.WEIGHTED_AVG, "delta_c": Op.AVG}

    def broadcast_payload(self, params, server_state):
        return {"params": params, "c": server_state["c"]}

    def server_init(self, params):
        return {"c": tree_zeros_like(params)}

    def client_init_state(self, params):
        return {"c_m": tree_zeros_like(params)}

    def client_update(self, payload, data, state):
        c, c_m = payload["c"], state["c_m"]

        def hook(w, g):  # g - c_m + c
            return jax.tree.map(lambda gg, cm, cc: gg - cm + cc, g, c_m, c)

        anchor = payload["params"]
        w, tau = self._local_sgd(anchor, data, hook)
        # option II update of the client variate
        c_m_new = jax.tree.map(
            lambda cm, cc, aa, ww: cm - cc + (aa - ww) / (tau * self.lr),
            c_m, c, anchor, w)
        delta = tree_sub(w, anchor)
        delta_c = tree_sub(c_m_new, c_m)
        return ClientResult({"delta": delta, "delta_c": delta_c}, self.ops(),
                            weight=float(data.n_samples)), {"c_m": c_m_new}

    def server_scalars(self, n_selected, n_total_clients):
        return {"frac": n_selected / max(n_total_clients, 1)}

    def server_update(self, params, agg, server_state, scalars):
        new = tree_add(params, agg["delta"], self.server_lr)
        # c += (M_p / M) * avg(delta_c); M_p folded in by the AVG op count
        c = tree_add(server_state["c"], agg["delta_c"], scalars["frac"])
        return new, {"c": c}

    def init_carry(self, payload, state):
        return {"w": payload["params"], "c": payload["c"],
                "c_m": state["c_m"]}

    def step_correction(self, carry, g):  # g - c_m + c
        return jax.tree.map(lambda gg, cm, cc: gg - cm + cc,
                            g, carry["c_m"], carry["c"])

    def finalize(self, carry, payload, state, batches, mask):
        anchor, w = payload["params"], carry["w"]
        c, c_m = carry["c"], carry["c_m"]
        tau = self._tau(mask)     # traced f32: cast back to the leaf dtype
        c_m_new = jax.tree.map(
            lambda cm, cc, aa, ww:
                (cm - cc + (aa - ww) / (tau * self.lr)).astype(cm.dtype),
            c_m, c, anchor, w)
        return ({"delta": tree_sub(w, anchor),
                 "delta_c": tree_sub(c_m_new, c_m)}, {"c_m": c_m_new})


class FedDyn(FLAlgorithm):
    """FedDyn (Acar et al., 2021): clients keep the gradient of their local
    regularised objective as state; the server keeps a drift corrector h."""
    name = "feddyn"
    stateful = True

    def __init__(self, *a, alpha: float = 0.1, **kw):
        super().__init__(*a, **kw)
        self.alpha = alpha

    def ops(self):
        return {"delta": Op.WEIGHTED_AVG}

    def server_init(self, params):
        return {"h": tree_zeros_like(params)}

    def client_init_state(self, params):
        return {"grad_corr": tree_zeros_like(params)}

    def client_update(self, payload, data, state):
        anchor = payload["params"]
        gc = state["grad_corr"]

        def hook(w, g):  # g + alpha * (w - anchor) - grad_corr
            return jax.tree.map(
                lambda gg, ww, aa, hh: gg + self.alpha * (ww - aa) - hh,
                g, w, anchor, gc)

        w, tau = self._local_sgd(anchor, data, hook)
        gc_new = jax.tree.map(lambda hh, ww, aa: hh - self.alpha * (ww - aa),
                              gc, w, anchor)
        delta = tree_sub(w, anchor)
        return ClientResult({"delta": delta}, self.ops(),
                            weight=float(data.n_samples)), {"grad_corr": gc_new}

    def server_scalars(self, n_selected, n_total_clients):
        # h^{r+1} = h^r - alpha * frac * delta_avg;
        # theta^{r+1} = avg(w) - h^{r+1}/alpha
        #            = theta^r + delta_avg * (1 + frac)   (telescoped form)
        frac = n_selected / max(n_total_clients, 1)
        return {"h_scale": -self.alpha * frac,
                "lr": self.server_lr * (1.0 + frac)}

    def server_update(self, params, agg, server_state, scalars):
        h = tree_add(server_state["h"], agg["delta"], scalars["h_scale"])
        new = tree_add(params, agg["delta"], scalars["lr"])
        return new, {"h": h}

    def init_carry(self, payload, state):
        return {"w": payload["params"], "anchor": payload["params"],
                "grad_corr": state["grad_corr"]}

    def step_correction(self, carry, g):  # g + alpha * (w - anchor) - h
        return jax.tree.map(
            lambda gg, ww, aa, hh: gg + self.alpha * (ww - aa) - hh,
            g, carry["w"], carry["anchor"], carry["grad_corr"])

    def finalize(self, carry, payload, state, batches, mask):
        anchor, w = payload["params"], carry["w"]
        gc_new = jax.tree.map(lambda hh, ww, aa: hh - self.alpha * (ww - aa),
                              state["grad_corr"], w, anchor)
        return {"delta": tree_sub(w, anchor)}, {"grad_corr": gc_new}


ALGORITHMS = {
    "fedavg": FedAvg,
    "fedprox": FedProx,
    "fednova": FedNova,
    "mime": Mime,
    "scaffold": Scaffold,
    "feddyn": FedDyn,
}


def make_algorithm(name: str, grad_fn: GradFn, lr: float, **kw) -> FLAlgorithm:
    return ALGORITHMS[name](grad_fn, lr, **kw)
