"""Serving state of the blocks of models/latent_moe.py (``attention_kind``
"mla", "gqa_sparse" and the layer patterns of "gqa"): a device-resident cache
of what each layer keeps for a session (per-token rows in pages, a
per-session recurrent state in a slot, or nothing), the table of sessions
that own its pages and slots, and the one entry point that extends a batch of
sessions by a block of new tokens each and returns each one's top-k next
items.

- The cache is, a layer, one array ``[pages * page, width]`` for each kind
  of row the block keeps (``row_layout``: the latent block one latent row,
  the sparse-index block a key/value row and an index row; widths are whole
  128-lane tiles), in the weights' dtype, plus one int32 array of the tokens
  themselves (the history mask reads it). Page 0 belongs to nobody: padding
  writes land there.
- A session is keyed by the query's ``user``. The table keeps the tokens it
  has cached; an incoming list reuses the longest prefix that equals them,
  token for token, and computes the rest (at least the last token, whose
  hidden state the answer needs). Least-recently-used sessions are evicted
  when pages run out. The answer never depends on the table: hit, partial
  hit, miss and eviction compute the same function of the incoming list.
- Block lengths come from a short ladder of buckets. The shortest bucket
  batches sessions and uses the absorbed attention form; longer blocks go
  one session a dispatch in the up-projected form, over a context no longer
  than the block when the whole session fits it (a cold session does). The
  choice of form is by block length alone, and so is what a bucket compiles
  to. A bucket of the SHORT block is ONE executable (``turn_step``: embed,
  every layer in order with its own weights, cache and counters, head +
  top-k; ``seq_turn_b<B>_t<T>_c<C>``): a turn is a few milliseconds of
  device work, and a launch a layer made the host's issuing its pace. A
  bucket of a longer block is three executables (embed, one layer, head +
  top-k) called a layer at a time: it compiles one layer whatever the depth,
  its device work is long beside its launches, and a piece that is not its
  block's last returns without a head. Both are compiled once by ``warmup``.
- The ladder is the block's own (``block_of(cfg).serve_shapes``), the rule
  is one: a short dispatch takes the smallest (batch, **context bucket**)
  that holds its sessions, so a lone turn reads its own session's rows and
  not a batch of whole-length contexts. The latent block's contexts are a
  quarter, a half and the whole of ``max_len``, for batches of up to 4 (a
  wider batch reads the whole length). The sparse-index block's: a turn runs
  alone over powers of two from twice ``index_topk`` to ``max_len``, in
  the ``select`` form; anything longer is cut into pieces that run in the
  ``chunk`` form, each writing its rows and attending to what the earlier
  pieces cached; only the last piece runs the head.
- A layer pattern (models/state_space.py) has five kinds of layer. An
  ``"A"`` layer keeps per-token key/value rows in pages, as above. An ``"S"``
  layer keeps a **per-session state** of fixed size (the recurrent state in
  ``state_dtype`` and the convolution's last inputs, one row each of
  ``[slots, values]`` arrays a layer), a ``"C"`` layer its convolution's
  last inputs alone (the block's ``state_layout`` a kind sizes a slot): a
  session owns its pages AND one slot, eviction frees both, slot 0 belongs
  to nobody (padding lands there), and a block that starts at offset 0
  starts from zeros whatever its slot held. A ``"W"`` (window attention)
  layer keeps in the session's slot a **ring** of its last
  ``sliding_window`` key/value rows and no page: what a session costs such a
  layer does not grow with its length (one ``[slots, window, width]`` array
  a layer). A pattern with ``"W"``
  layers cuts a block longer than its piece (``PIECE_TILES x index_kv_tile``
  tokens) into pieces, each resuming from the rings and the ``"A"`` layers'
  pages the pieces before it left, the lock offered between them. An
  ``"E"`` or a ``"D"`` layer
  keeps nothing. A state stands at exactly
  one position, the length last computed, so the **reuse rule** is: a
  session's state stands at n tokens; an incoming list whose first n tokens
  equal the cached ones and which is LONGER continues from the state
  (tokens n.. are computed); any other list (it diverges before n, it slid
  past ``max_len``, it is shorter, or it is the same list again) is computed
  from position 0, and ``pio_seq_state_restarts_total`` counts it when a
  prefix did match. (An unchanged list sent again is a restart: nothing of
  the last answer is kept, and the last token cannot be recomputed from a
  state that already holds it.) A long bucket compiles one program a layer
  kind (``seq_ssm_b<B>_t<T>``, ``seq_moe_b<B>_t<T>``, ``seq_conv_b<B>_t<T>``,
  ``seq_ffn_b<B>_t<T>``, ``seq_win_b<B>_t<T>``: no context, shared by the
  bucket's contexts;
  ``seq_gqa_b<B>_t<T>_c<C>``), called in pattern
  order; the pieces of a cut block hand the state on through the slot.
- One dispatch runs at a time (``_TurnLock``), and between the pieces of a
  cut block the lock is offered to whoever waits: another batch's turns run
  between a miss's pieces and do not wait for all of it. A batch that names
  a session whose block is still being cut waits for that block.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import functools
import re
import threading
import weakref
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from incubator_predictionio_tpu.models import latent_moe
from incubator_predictionio_tpu.obs.metrics import REGISTRY
from incubator_predictionio_tpu.obs.trace import span

_TOKENS_COMPUTED = REGISTRY.counter(
    "pio_seq_tokens_computed_total",
    "Session tokens run through the block by the sequence template")
_TOKENS_REUSED = REGISTRY.counter(
    "pio_seq_tokens_reused_total",
    "Session tokens answered from the latent cache (prefix already held)")
_EVICTIONS = REGISTRY.counter(
    "pio_seq_cache_evictions_total",
    "Sessions evicted from the latent cache (least recently used first)")
_CACHE_TOKENS = REGISTRY.gauge(
    "pio_seq_cache_tokens", "Latent cache tokens by state (used, capacity)",
    ("state",))
_DISPATCHES = REGISTRY.counter(
    "pio_seq_dispatches_total",
    "Extend dispatches by (batch x block) bucket", ("bucket",))
_LAUNCHES = REGISTRY.counter(
    "pio_seq_launches_total",
    "Executables called by extend dispatches, by block (short: one turn "
    "program a dispatch; long: embed, one a layer, the head where it answers)",
    ("block",))
_EXPERT_TOKENS = REGISTRY.counter(
    "pio_moe_expert_tokens_total",
    "Token-picks routed to each expert held on this chip", ("layer", "expert"))
_UNHELD = REGISTRY.counter(
    "pio_moe_tokens_unheld_total",
    "Token-picks that fell on experts held on other chips", ("layer",))
_TOUCHED = REGISTRY.counter(
    "pio_moe_experts_touched_total",
    "Held experts that received at least one pick, summed over dispatches",
    ("layer",))
_EXPERT_LAYERS = REGISTRY.counter(
    "pio_moe_expert_layers_total",
    "Expert layers run by dispatches, by the grouped matmul their programs "
    "were compiled with (kernel: ops/grouped_matmul.py, the one form on a "
    "TPU; ragged: jax.lax.ragged_dot, a process with no kernel backend)",
    ("form",))
_CONTEXT_HELD = REGISTRY.counter(
    "pio_seq_context_rows_held_total",
    "Tokens of the sessions of short-block dispatches, their blocks included")
_CONTEXT_READ = REGISTRY.counter(
    "pio_seq_context_rows_read_total",
    "Cache rows short-block dispatches read: batch x context of the bucket")
_PREFILL_CHUNKS = REGISTRY.counter(
    "pio_seq_prefill_chunks_total",
    "Long-block dispatches: the pieces a long block was cut into (a block "
    "the ladder holds whole is one)")
_STATE_SLOTS = REGISTRY.gauge(
    "pio_seq_state_slots",
    "Per-session state slots by state (used, capacity)", ("state",))
_STATE_EVICTIONS = REGISTRY.counter(
    "pio_seq_state_evictions_total",
    "Sessions evicted whose state slot was freed with their pages")
_STATE_RESTARTS = REGISTRY.counter(
    "pio_seq_state_restarts_total",
    "Lists computed from position 0 although a prefix matched: the state "
    "stood elsewhere (a diverging, shorter or unchanged list)")
_STATE_TOKENS = REGISTRY.counter(
    "pio_seq_state_tokens_total",
    "Tokens run through the state-space layers by form (step: short blocks "
    "from cached states; scan: long blocks)", ("form",))
_STATE_STEP_SESSIONS = REGISTRY.counter(
    "pio_seq_state_step_sessions_total",
    "Sessions in short-block dispatches of a stateful pattern")
_WINDOW_HELD = REGISTRY.counter(
    "pio_seq_window_rows_held_total",
    "Key/value rows a window layer read for the sessions of short-block "
    "dispatches: each session's ring rows before its block, and the block "
    "(a layer; every window layer reads the same)")
_WINDOW_UNWINDOWED = REGISTRY.counter(
    "pio_seq_window_rows_unwindowed_total",
    "Rows the same layer would have read for them without the window: every "
    "token of each session, its block included")
TOP_K = 16                       # the head's k the ladder is warmed for
#: a pattern's layer kinds: the names of their programs, and which take no
#: context (one program a (batch, block), shared by the bucket's contexts)
PROGRAM = {"S": "ssm", "A": "gqa", "E": "moe", "C": "conv", "D": "ffn",
           "W": "win"}
CONTEXT_FREE = ("S", "E", "C", "D", "W")
_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%?([\w.\-]+) = (.*)op_name="([^"]*)"', re.M)
#: control flow has no device time of its own: a trace shows a loop's
#: operations inside the loop's own event, and a sum over both counts the
#: body twice
_CONTAINER = re.compile(r" (?:while|conditional|call)\(")


@dataclasses.dataclass
class _Session:
    tokens: np.ndarray            # what the cache holds for it, in order
    pages: list
    given: Optional[Sequence] = None   # the list ``tokens`` was encoded from
    slot: int = 0                 # its per-session state (0: none kept)


@dataclasses.dataclass
class _Block:
    """One request as matched: compute ``tokens[offset:]`` at ``offset``."""
    row: int
    tokens: np.ndarray
    offset: int
    pages: list
    slot: int = 0


def _bucket(ladder: Sequence[int], n: int) -> int:
    return next(b for b in ladder if b >= n)


@functools.lru_cache(maxsize=None)
def _traced_once(step):
    """A layer kind's step under a jit of its own: inside ``turn_step``'s
    one program a kind is then traced and lowered once a bucket, not once a
    layer (a deploy pays that for every bucket, compile cache or not); the
    compiler inlines the calls, the executable is the same."""
    return jax.jit(step, static_argnames=("cfg", "form"))


def instruction_scopes(text: str, scopes: Sequence[str]) -> dict:
    """``{HLO instruction: named scope}`` of one compiled program's text, by
    the innermost of ``scopes`` on each instruction's ``op_name`` path. Loops
    and branches are left out: their time is their bodies' operations',
    which are in the map."""
    found = {}
    for name, body, op in _INSTRUCTION.findall(text):
        if _CONTAINER.search(body):
            continue
        path = op.split("/")
        parts = [p for p in path if p in scopes]
        if parts:
            found[name] = parts[-1]
        elif path[-1].startswith("ragged-dot"):
            # the TPU compiler's grouped-matmul kernel comes back without
            # the scope it was traced under (under a jit inside the program
            # it keeps that jit's name in front); the routed experts are the
            # only grouped matmul here
            found[name] = "moe_experts"
    return found


def split_operands(operands, block: int) -> tuple:
    """A short dispatch's ONE int32 operand ``[B, block + P + 3]`` as its
    parts ``(tokens [B, block], pages [B, P], offsets [B], counts [B],
    slots [B])``: views to fill of the host's array, slices inside the
    program. (One array because every numpy operand of a launch is a
    transfer of its own, ~0.14 ms each on the chip: PERF.md PR 37.)"""
    return (operands[:, :block], operands[:, block:-3], operands[:, -3],
            operands[:, -2], operands[:, -1])


#: ``turn_step``'s arguments that come back: the token cache, every layer's
#: cache and every layer's counters (donated where the backend reuses them)
TURN_KEPT = (1, 3, 4)


def turn_step(item_emb, tok_cache, layers, caches, counters, norm_f, head,
              tokens, pages, offsets, counts, slots, *, cfg, form, k):
    """A whole dispatch of the short block as one function: ``embed_step``,
    every layer's own step (``latent_moe.step_of``) in ``layer_kinds`` order
    on its own weights, cache and counters, ``head_step`` at ``k``: the same
    functions in the same order as a long block's chain calls one by one.
    Returns ``((values, tokens), tok_cache, caches, counters)``."""
    h, tok_cache = latent_moe.embed_step(
        item_emb, tok_cache, tokens, pages, offsets, counts,
        page=cfg.cache_page)
    caches, counters = list(caches), list(counters)
    for i, kind in enumerate(latent_moe.layer_kinds(cfg)):
        step = _traced_once(latent_moe.step_of(kind, cfg))
        h, caches[i], counters[i] = step(
            layers[i], caches[i], counters[i], h,
            slots if kind in CONTEXT_FREE else pages, offsets, counts,
            cfg=cfg, form=form)
    out = latent_moe.head_step(norm_f, head, tok_cache, h, pages, offsets,
                               counts, cfg=cfg, k=k)
    return out, tok_cache, caches, counters


class _TurnLock:
    """A lock handed on in arrival order, which its holder can offer to those
    who wait (``offer``: they all run before the holder goes on). A plain
    ``threading.Lock`` released and taken again goes back to the thread that
    released it."""

    def __init__(self):
        self._cond = threading.Condition()
        self._next = self._serving = 0

    def __enter__(self):
        with self._cond:
            mine, self._next = self._next, self._next + 1
            while self._serving != mine:
                self._cond.wait()
        return self

    def __exit__(self, *exc):
        with self._cond:
            self._serving += 1
            self._cond.notify_all()

    def ahead(self) -> int:
        """Tickets not yet served, the holder's included (read unlocked: a
        span's attribute, nothing decides by it)."""
        return self._next - self._serving

    def offer(self) -> bool:
        """Lets every thread that waits now run first; false if none does."""
        if self.ahead() < 2:
            return False
        self.__exit__()
        self.__enter__()
        return True


class LatentServing:
    def __init__(self, params: dict, cfg):
        self.cfg, self.params = cfg, params
        self.page = cfg.cache_page
        self.block = latent_moe.block_of(cfg)
        self.shapes = self.block.serve_shapes(cfg)
        self.blocks = self.shapes.blocks
        self.batches = self.shapes.batches
        self.device = next(iter(params["item_emb"].devices()))
        self.kinds = latent_moe.layer_kinds(cfg)
        self.layout = self.block.row_layout(cfg)
        wdt = params["item_emb"].dtype
        paged = sum(k in (latent_moe.LAYER, "A") for k in self.kinds)
        self.bytes_per_token = paged * sum(self.layout.values()) \
            * wdt.itemsize + 4
        # what a layer of a stateful kind ("S", "C", "W") keeps for a
        # session: {kind: {name: (values, dtype)}}, ``values`` a count or
        # the shape of a slot's share (a ring's (rows, width))
        self.state_layout = {
            kind: self.block.state_layout(cfg, kind)
            for kind in (self.block.STATEFUL if cfg.layer_pattern else ())
            if kind in self.kinds}
        self.state_bytes_per_session = sum(
            self.kinds.count(kind) * int(np.prod(n)) * dt.itemsize
            for kind, layout in self.state_layout.items()
            for n, dt in layout.values())
        self.window = cfg.sliding_window if "W" in self.kinds else 0
        # ``cache_tokens`` is the operator's: live sessions x the length they
        # may reach (default: 16 sessions of ``max_len``); never less than
        # two whole sessions. Page 0 belongs to nobody.
        tokens = cfg.cache_tokens or 16 * cfg.max_len
        n_pages = max(tokens, 2 * cfg.max_len) // self.page + 1
        rows = n_pages * self.page
        # ``state_slots`` is the operator's too: sessions whose state the
        # device keeps (default: what ``tokens / max_len`` sessions need).
        # Slot 0 belongs to nobody.
        self.n_slots = (cfg.state_slots or max(tokens // cfg.max_len, 2)) \
            if self.state_layout else 0

        def kept(kind):
            if kind in self.state_layout:
                return {name: jnp.zeros(
                    (self.n_slots + 1, *np.atleast_1d(n)), dt)
                    for name, (n, dt) in self.state_layout[kind].items()}
            if kind not in (latent_moe.LAYER, "A"):
                return {}
            return {name: jnp.zeros((rows, width), wdt)
                    for name, width in self.layout.items()}

        self.moe_layers = [i for i, k in enumerate(self.kinds)
                           if k in (latent_moe.LAYER, "E")]
        # (the grouped matmul every bucket's expert layers are compiled with)
        self.expert_form = latent_moe.expert_form(
            params["layers"][self.moe_layers[0]]["we1"].shape)
        with jax.default_device(self.device):
            self.cache = [kept(kind) for kind in self.kinds]
            self.tok_cache = jnp.zeros((rows,), jnp.int32)
            # (the experts' device counters; ``()`` where a layer has none)
            self.counters = [
                jnp.zeros((latent_moe.experts_held(cfg)
                           + latent_moe.N_EXTRA_COUNTERS,), jnp.int32)
                if i in self.moe_layers else ()
                for i in range(cfg.n_layers)]
        self.capacity_tokens = (n_pages - 1) * self.page
        self._free = list(range(n_pages - 1, 0, -1))
        self._free_slots = list(range(self.n_slots, 0, -1))
        self._sessions: "collections.OrderedDict[str, _Session]" = \
            collections.OrderedDict()
        self._exe: dict = {}
        self._shared: dict = {}      # (kind, batch, block) -> executable
        self._lock = _TurnLock()
        self._cutting: set = set()   # sessions of an ``extend`` under way
        self._published = np.zeros(
            (len(self.moe_layers), latent_moe.experts_held(cfg)
             + latent_moe.N_EXTRA_COUNTERS), np.int64)
        _CACHE_TOKENS.labels(state="capacity").set(self.capacity_tokens)
        if self.n_slots:
            _STATE_SLOTS.labels(state="capacity").set(self.n_slots)
        # weakly: the registry must not keep a retired deployment's cache
        # and weights on the device
        me, key = weakref.ref(self), f"latent_serving:{id(self)}"
        REGISTRY.add_collector(key, lambda: me() and me()._collect())
        self._finalizer = weakref.finalize(
            self, REGISTRY.remove_collector, key)

    # -- executables --------------------------------------------------------------
    def form(self, block: int) -> str:
        return self.shapes.short_form if block == self.blocks[0] \
            else self.shapes.long_form

    def ladder(self) -> list:
        """Every (batch, block, context) bucket a dispatch can take. The
        latent block: short blocks attend over a context bucket in batches
        of up to 4 and over the whole length in wider ones; a long block
        whose session fits the block itself (a cold session does) attends
        over just that, else over the whole length; a layer pattern's
        attention layers the same. The sparse-index block: short blocks and
        pieces of long ones over every context bucket that holds them."""
        out = [(b, self.blocks[0], c) for b in self.batches
               for c in self.shapes.contexts(b)]
        for t in self.blocks[1:]:
            out += [(1, t, c) for c in self.shapes.long_contexts(t)]
        return out

    @staticmethod
    def label(batch: int, block: int, ctx: int) -> str:
        return f"{batch}x{block}@{ctx}"

    def _compile(self, batch: int, block: int, ctx: int) -> dict:
        """A bucket's executables: the one turn program for the short block
        (``{"turn": {k: executable}}``), the chain for any other."""
        if block == self.blocks[0]:
            return {"turn": {
                TOP_K: self._lower_turn(batch, block, ctx, TOP_K).compile()}}
        # a context-free program is compiled once a (batch, block)
        done = {k: self._shared[k, batch, block] for k in CONTEXT_FREE
                if (k, batch, block) in self._shared}
        exe = {k: v.compile() for k, v in self._lower(
            batch, block, ctx, skip=tuple(done)).items()}
        self._shared.update({(k, batch, block): exe[k]
                             for k in CONTEXT_FREE if k in exe})
        return {**exe, **done, "head": {
            TOP_K: self._compile_head(batch, block, ctx, TOP_K)}}

    def program(self, kind: str, batch: int, block: int, ctx: int) -> str:
        """The name a device trace shows a bucket's program under, less the
        ``jit_``: ``seq_turn_b<B>_t<T>_c<C>`` (the short block's one
        program); a longer block's ``seq_<embed|layer|head>_b<B>_t<T>_c<C>``,
        and for a pattern's kinds ``seq_<ssm|moe>_b<B>_t<T>``,
        ``seq_gqa_b<B>_t<T>_c<C>``."""
        tag = f"b{batch}_t{block}" + (
            "" if kind in CONTEXT_FREE else f"_c{ctx}")
        return f"seq_{PROGRAM.get(kind, kind)}_{tag}"

    def _lower_turn(self, batch: int, block: int, ctx: int, k: int):
        """``turn_step`` at the bucket's shapes, its five small operands as
        one array (``split_operands``), lowered under the name a device
        trace shows; the token cache, the caches and the counters are
        donated and come back."""
        cfg, form = self.cfg, self.form(block)

        def turn(*args):
            return turn_step(*args[:-1], *split_operands(args[-1], block),
                             cfg=cfg, form=form, k=k)

        turn.__name__ = turn.__qualname__ = self.program(
            "turn", batch, block, ctx)
        # (the CPU backend cannot reuse a donated buffer and says so)
        keep = self.device.platform == "cpu"
        with jax.default_device(self.device):
            return jax.jit(
                turn, donate_argnums=() if keep else TURN_KEPT).lower(
                self.params["item_emb"], self.tok_cache,
                self.params["layers"], self.cache, self.counters,
                self.params["norm_f"], latent_moe.head_matrix(self.params),
                jax.ShapeDtypeStruct(
                    (batch, block + ctx // self.page + 3), jnp.int32))

    def _lower(self, batch: int, block: int, ctx: int, skip=()) -> dict:
        """A long bucket's embed program and its layer programs, one a layer
        kind (``"layer"`` where every layer is the same), lowered under the
        names a device trace shows."""
        cfg, page = self.cfg, self.page

        def named(fn, kind):
            fn.__name__ = fn.__qualname__ = self.program(
                kind, batch, block, ctx)
            return fn

        def spec(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype)

        small = (spec((batch, ctx // page), jnp.int32),
                 spec((batch,), jnp.int32), spec((batch,), jnp.int32))
        h = spec((batch, block, cfg.d_model), jnp.float32)
        form = self.form(block)
        # (the CPU backend cannot reuse a donated buffer and says so)
        keep = self.device.platform == "cpu"
        out = {}
        with jax.default_device(self.device):
            out["embed"] = jax.jit(named(
                lambda emb, toks, tokens, pages, offsets, counts:
                latent_moe.embed_step(emb, toks, tokens, pages, offsets,
                                      counts, page=page), "embed"),
                donate_argnums=() if keep else (1,)).lower(
                self.params["item_emb"], self.tok_cache,
                spec((batch, block), jnp.int32), *small)
            for kind in dict.fromkeys(self.kinds):
                if kind in skip:
                    continue
                first = self.kinds.index(kind)
                # (a context-free kind takes the sessions' slots where the
                # others take their pages; the argument keeps the name the
                # accepted programs' texts were pinned under)
                own = small[1] if kind in CONTEXT_FREE else small[0]
                fn = (lambda step: lambda lw, cache, counters, h, pages,
                      offsets, counts: step(
                          lw, cache, counters, h, pages, offsets, counts,
                          cfg=cfg, form=form))(latent_moe.step_of(kind, cfg))
                out[kind] = jax.jit(
                    named(fn, kind),
                    donate_argnums=() if keep else (1, 2, 3)).lower(
                    self.params["layers"][first], self.cache[first],
                    self.counters[first], h, own, *small[1:])
        return out

    def _compile_head(self, batch: int, block: int, ctx: int, k: int):
        cfg = self.cfg

        def head(norm_f, head, toks, h, pages, offsets, counts):
            return latent_moe.head_step(norm_f, head, toks, h, pages, offsets,
                                        counts, cfg=cfg, k=k)

        head.__name__ = head.__qualname__ = self.program(
            "head", batch, block, ctx)
        int32 = jnp.int32
        with jax.default_device(self.device):
            return jax.jit(head).lower(
                self.params["norm_f"], latent_moe.head_matrix(self.params),
                self.tok_cache,
                jax.ShapeDtypeStruct((batch, block, cfg.d_model), jnp.float32),
                jax.ShapeDtypeStruct((batch, ctx // self.page), int32),
                jax.ShapeDtypeStruct((batch,), int32),
                jax.ShapeDtypeStruct((batch,), int32)).compile()

    def warmup(self, max_batch: int = 64) -> int:
        """Compiles every bucket of the ladder and runs it once on page 0
        (one ``deploy.warmup.bucket`` span each). Where the block batches its
        short blocks, batches go up to ``max_batch``."""
        if self.shapes.batch_to_max:
            self.batches = tuple(
                b for b in self.shapes.batches if b < max_batch) + (max_batch,)
        with span("deploy.warmup", buckets=len(self.ladder())):
            for bucket in self.ladder():
                with span("deploy.warmup.bucket", bucket=self.label(*bucket),
                          path=f"latent-{self.form(bucket[1])}"):
                    self._exe[bucket] = self._compile(*bucket)
                    empty = _Block(0, np.zeros(0, np.int32), 0, [])   # slot 0
                    self._dispatch([empty], *bucket, count=False)
        return len(self._exe)

    def device_scopes(self) -> dict:
        """``{executable name: {HLO instruction: named scope}}`` from the
        compiled programs' own metadata (``instruction_scopes``): a device
        trace names operations by instruction, and this is what tells
        ``moe_experts`` from ``mla_attn`` inside one executable."""
        out, scopes = {}, latent_moe.scopes(self.cfg)
        for (batch, block, ctx), exes in self._exe.items():
            kinds = ("turn",) if "turn" in exes \
                else (*dict.fromkeys(self.kinds), "head")
            for kind in kinds:
                exe = exes[kind][TOP_K] if kind in ("turn", "head") \
                    else exes[kind]
                out["jit_" + self.program(kind, batch, block, ctx)] = \
                    instruction_scopes(exe.as_text(), scopes)
        return out

    # -- the session table ---------------------------------------------------------
    def _evict(self, busy: set) -> None:
        """Frees the least recently used session's pages and slot."""
        victim = next((k for k in self._sessions if k not in busy), None)
        if victim is None:
            raise RuntimeError(
                "the session cache is too small for this batch "
                f"({self.capacity_tokens} tokens, {self.n_slots} state "
                "slots)")
        self._drop(self._sessions.pop(victim), self._free, self._free_slots)
        _EVICTIONS.inc()
        if self.n_slots:
            _STATE_EVICTIONS.inc()

    @staticmethod
    def _drop(sess: _Session, pages: list, slots: list) -> None:
        """A session leaves the table: its pages and its slot go to the
        free lists, or to those a dispatch under way frees when it ends."""
        pages.extend(sess.pages)
        if sess.slot:
            slots.append(sess.slot)

    def _take_pages(self, n: int, busy: set) -> list:
        while len(self._free) < n:
            self._evict(busy)
        return [self._free.pop() for _ in range(n)]

    def _take_slot(self, busy: set) -> int:
        if not self.n_slots:
            return 0
        while not self._free_slots:
            self._evict(busy)
        return self._free_slots.pop()

    def _tokens(self, key: Optional[str], given: tuple, encode) -> np.ndarray:
        """The session as int32 tokens. A turn sends again a list the table
        has seen: what equals the list as it was last given is not encoded a
        second time (``encode`` maps item by item and keeps the last
        ``max_len`` tokens, so the tokens of a list's head are the head of
        its tokens)."""
        sess = self._sessions.get(key) if key is not None else None
        n = len(sess.given) if sess is not None and sess.given else 0
        if n and given[:n] == sess.given:
            return np.concatenate([sess.tokens, np.asarray(
                encode(given[n:]), np.int32)])[-self.cfg.max_len:]
        return np.asarray(encode(given), np.int32)

    def _match(self, row: int, key: Optional[str], tokens: np.ndarray,
               given: Optional[tuple], release: tuple) -> _Block:
        """``release``: the (pages, slots) to free after the dispatch."""
        sess = self._sessions.get(key) if key is not None else None
        new = sess is None
        if new:
            sess, reuse = _Session(tokens[:0], []), 0
        else:
            n = min(len(sess.tokens), len(tokens))
            differ = np.flatnonzero(sess.tokens[:n] != tokens[:n])
            reuse = int(differ[0]) if len(differ) else n
        if not self.n_slots:
            reuse = min(reuse, len(tokens) - 1)
        elif reuse and not reuse == len(sess.tokens) < len(tokens):
            # the state stands at len(sess.tokens) and nowhere else: only a
            # longer list that begins with all of it continues from there
            reuse = 0
            _STATE_RESTARTS.inc()
        need = -(-len(tokens) // self.page) - len(sess.pages)
        try:
            if need > 0:
                sess.pages = sess.pages + self._take_pages(
                    need, self._cutting)
            if new:
                sess.slot = self._take_slot(self._cutting)
        except RuntimeError:
            if new:   # the table does not know it yet: ``extend`` cannot
                self._drop(sess, *release)
            raise
        if need < 0:
            release[0].extend(sess.pages[need:])
            sess.pages = sess.pages[:need]
        sess.tokens, sess.given = tokens, given
        block = _Block(row, tokens, reuse, list(sess.pages), sess.slot)
        if key is not None:
            self._sessions[key] = sess
            self._sessions.move_to_end(key)   # most recently used
        else:
            self._drop(sess, *release)        # nobody can come back to it
        _TOKENS_REUSED.inc(reuse)
        _TOKENS_COMPUTED.inc(len(tokens) - reuse)
        return block

    # -- the entry point -------------------------------------------------------------
    def extend(self, requests: Sequence[tuple], encode=None,
               num: int = TOP_K) -> tuple:
        """``[(key or None, session)]`` → ``(scores [R, k], tokens [R, k])``
        of each session's last position, best first, padding and the
        session's own tokens masked; ``k >= num``. ``encode`` turns a session
        as given (a sequence of items) into its int32 tokens, item by item
        (default: it is them already). A session with no token gets a row of
        ``-inf``. Safe to call from several threads: dispatches run one at a
        time, and between the pieces of a cut block the other callers' run
        (module docstring)."""
        k = TOP_K if num <= TOP_K else min(
            1 << (num - 1).bit_length(), self.cfg.vocab_size)
        busy = {key for key, _ in requests if key is not None}
        with contextlib.ExitStack() as held:
            with span("seq.batch.lock", ahead=self._lock.ahead()):
                held.enter_context(self._lock)
                # a session whose block another caller is still cutting:
                # after it
                while busy & self._cutting and self._lock.offer():
                    pass
            self._cutting |= busy
            release: tuple = ([], [])
            try:
                with span("seq.batch.match", sessions=len(requests)) as sp:
                    blocks, slots_before = [], len(self._free_slots)
                    for row, (key, session) in enumerate(requests):
                        if encode is None:
                            given, tokens = None, np.asarray(session, np.int32)
                        else:
                            given = tuple(session)
                            tokens = self._tokens(key, given, encode)
                        if len(tokens):
                            blocks.append(
                                self._match(row, key, tokens, given, release))
                    hits = sum(b.offset > 0 for b in blocks)
                    sp.set_attr("hits", hits)
                    sp.set_attr("misses", len(blocks) - hits)
                    sp.set_attr("reused", sum(b.offset for b in blocks))
                    if self.n_slots:
                        sp.set_attr("slots_taken", max(
                            slots_before - len(self._free_slots), 0))
                scores = np.full((len(requests), k), -np.inf, np.float32)
                items = np.zeros((len(requests), k), np.int32)
                for group, bucket, last in self._plan(
                        [requests[b.row][0] for b in blocks], blocks):
                    out = self._dispatch(group, *bucket, k, head=last)
                    if last:
                        rows = [b.row for b in group]
                        scores[rows], items[rows] = \
                            out[0][:len(rows)], out[1][:len(rows)]
                    else:   # between a cut block's pieces
                        with span("seq.batch.lock", why="offer"):
                            self._lock.offer()
            except BaseException:
                # the table says more of these sessions than the cache holds
                for sess in map(self._sessions.pop, busy & set(self._sessions)):
                    self._drop(sess, *release)
                raise
            finally:
                self._cutting -= busy
                self._free.extend(release[0])
                self._free_slots.extend(release[1])
                used = sum(len(s.pages) for s in self._sessions.values())
                _CACHE_TOKENS.labels(state="used").set(used * self.page)
                if self.n_slots:
                    _STATE_SLOTS.labels(state="used").set(
                        self.n_slots - len(self._free_slots))
        return scores, items

    def _plan(self, keys, blocks):
        """Dispatches in order, as ``(group, bucket, last)``: ``last`` is
        false for every piece of a cut block but its final one, whose head
        alone answers. Two requests of one session never share a dispatch
        (they would write the same pages): the later one waits for the next
        round."""
        rounds, depth = [], {}
        for key, blk in zip(keys, blocks):
            r = depth.get(key, 0) if key is not None else 0
            if key is not None:
                depth[key] = r + 1
            while len(rounds) <= r:
                rounds.append([])
            rounds[r].append(blk)
        short, longest = self.blocks[0], self.blocks[-1]
        for members in rounds:
            quick = [b for b in members if len(b.tokens) - b.offset <= short]
            for i in range(0, len(quick), self.batches[-1]):
                group = quick[i:i + self.batches[-1]]
                yield group, self._short_bucket(
                    len(group), max(len(b.tokens) for b in group)), True
            for b in members:
                if len(b.tokens) - b.offset <= short:
                    continue
                for start in range(b.offset, len(b.tokens), longest):
                    end = min(start + longest, len(b.tokens))
                    block = _bucket(self.blocks, end - start)
                    piece = _Block(b.row, b.tokens[:end], start, b.pages,
                                   b.slot)
                    if block == short:   # a cut block's tail, as a turn
                        bucket = self._short_bucket(1, end)
                    else:
                        bucket = (1, block, _bucket(
                            self.shapes.long_contexts(block), end))
                    yield [piece], bucket, end == len(b.tokens)

    def _short_bucket(self, sessions: int, longest: int) -> tuple:
        """The smallest (batch, context) a short dispatch of ``sessions``
        fits, the longest of them ``longest`` tokens with its block."""
        batch = _bucket(self.batches, sessions)
        return batch, self.blocks[0], _bucket(
            self.shapes.contexts(batch), longest)

    def _at_k(self, batch: int, block: int, ctx: int, k: int):
        """The executable of a bucket that holds the head + top-k (the short
        block's turn program, a longer block's head), at ``k``; the ladder
        is warmed at ``TOP_K``, a larger ``num`` compiles its own when first
        asked for."""
        exe = self._exe[batch, block, ctx]
        if "turn" in exe:
            if k not in exe["turn"]:
                exe["turn"][k] = self._lower_turn(
                    batch, block, ctx, k).compile()
            return exe["turn"][k]
        if k not in exe["head"]:
            exe["head"][k] = self._compile_head(batch, block, ctx, k)
        return exe["head"][k]

    def _dispatch(self, group: list, batch: int, block: int, ctx: int,
                  k: int = TOP_K, count: bool = True, head: bool = True):
        """Embed, every layer, and with ``head`` the head + top-k, whose
        ``(values, tokens)`` come back; a piece that is not its block's last
        only leaves its rows in the cache. The short block (turns, a cut
        block's tail, a short miss: always its block's last piece) is ONE
        launch of the bucket's turn program; any other block is the chain,
        a launch a layer. Three child spans cover the dispatch,
        ``seq.turn.*`` for the short block and ``seq.miss.*`` for any other:
        ``stage`` (the operands), ``launch`` (what the host spends issuing
        the programs) and, with ``head``, ``wait`` (the device finishing and
        the transfer back; without it nothing is waited for, and the piece's
        device work runs on under whatever comes next)."""
        exe = self._exe[batch, block, ctx]
        form = self.form(block)
        short = block == self.blocks[0]
        n_new = sum(len(b.tokens) - b.offset for b in group)
        scope = "seq.turn" if short else "seq.miss"
        launches = 1 if short else len(self.kinds) + 1 + head
        with span("seq.batch.extend", bucket=self.label(batch, block, ctx),
                  tokens=n_new, form=form):
            with span(scope + ".stage", sessions=len(group)):
                n_pages = ctx // self.page
                if short:   # the turn program's one operand, filled by part
                    operands = np.zeros(
                        (batch, block + n_pages + 3), np.int32)
                    tokens, pages, offsets, counts, slots = split_operands(
                        operands, block)
                else:
                    tokens, pages, offsets, counts, slots = (
                        np.zeros(shape, np.int32) for shape in (
                            (batch, block), (batch, n_pages), (batch,),
                            (batch,), (batch,)))
                for i, b in enumerate(group):
                    new = b.tokens[b.offset:]
                    tokens[i, :len(new)] = new
                    # a piece's context ends with it
                    held = b.pages[:pages.shape[1]]
                    pages[i, :len(held)] = held
                    offsets[i], counts[i], slots[i] = \
                        b.offset, len(new), b.slot
                small = (pages, offsets, counts)
                if self.cfg.layer_pattern and not short:
                    # (one transfer for the whole stack's launches, not one
                    # a layer: a pattern is many thin layers)
                    small = jax.device_put(small, self.device)
                    slots = jax.device_put(slots, self.device)
            with span(scope + ".launch", launches=launches):
                if short:
                    out, self.tok_cache, self.cache, self.counters = \
                        self._at_k(batch, block, ctx, k)(
                            self.params["item_emb"], self.tok_cache,
                            self.params["layers"], self.cache, self.counters,
                            self.params["norm_f"],
                            latent_moe.head_matrix(self.params), operands)
                else:
                    h, self.tok_cache = exe["embed"](
                        self.params["item_emb"], self.tok_cache, tokens,
                        *small)
                    for i, (kind, lw) in enumerate(
                            zip(self.kinds, self.params["layers"])):
                        own = slots if kind in CONTEXT_FREE else small[0]
                        h, self.cache[i], self.counters[i] = exe[kind](
                            lw, self.cache[i], self.counters[i], h, own,
                            *small[1:])
                    out = self._at_k(batch, block, ctx, k)(
                        self.params["norm_f"],
                        latent_moe.head_matrix(self.params),
                        self.tok_cache, h, *small) if head else None
            if head:
                with span(scope + ".wait"):
                    out = jax.device_get(out)
        if count:
            _DISPATCHES.labels(bucket=self.label(batch, block, ctx)).inc()
            _LAUNCHES.labels(block="short" if short else "long").inc(launches)
            _EXPERT_LAYERS.labels(form=self.expert_form).inc(
                len(self.moe_layers))
            if not short:
                _PREFILL_CHUNKS.inc()
            else:
                _CONTEXT_HELD.inc(sum(len(b.tokens) for b in group))
                _CONTEXT_READ.inc(batch * ctx)
                if self.window:
                    _WINDOW_UNWINDOWED.inc(sum(len(b.tokens) for b in group))
                    _WINDOW_HELD.inc(sum(
                        min(b.offset, self.window) + len(b.tokens) - b.offset
                        for b in group))
            if self.n_slots:
                _STATE_TOKENS.labels(form=form).inc(n_new)
                if short:
                    _STATE_STEP_SESSIONS.inc(len(group))
            self.block.count_dispatch(self.cfg, [
                (b.offset, len(b.tokens) - b.offset) for b in group])
        return out

    # -- what the status page and /metrics show ----------------------------------------
    def _collect(self) -> None:
        """The device's per-layer expert counters, read when ``/metrics`` is
        read: the counters families advance by what was added since."""
        with self._lock:
            now = np.stack(jax.device_get(
                [self.counters[i] for i in self.moe_layers])).astype(np.int64)
        delta, self._published = now - self._published, now
        held = latent_moe.experts_held(self.cfg)
        for layer, row in zip(self.moe_layers, delta):
            for j in np.flatnonzero(row[:held]):
                _EXPERT_TOKENS.labels(
                    layer=str(layer),
                    expert=str(self.cfg.expert_offset + j)).inc(int(row[j]))
            _UNHELD.labels(layer=str(layer)).inc(int(row[held]))
            _TOUCHED.labels(layer=str(layer)).inc(int(row[held + 1]))

    def info(self) -> dict:
        cfg = self.cfg
        return {
            "path": self.shapes.path,
            "cache_capacity_tokens": self.capacity_tokens,
            "cache_page": self.page,
            "cache_bytes_per_token": self.bytes_per_token,
            "cache_row_widths": dict(self.layout),
            **({"state_slots": self.n_slots,
                "state_bytes_per_session": self.state_bytes_per_session,
                "layer_pattern": cfg.layer_pattern} if self.n_slots else {}),
            **({"sliding_window": self.window} if self.window else {}),
            "sessions": len(self._sessions),
            "buckets": [f"{self.label(*b)}:{self.form(b[1])}"
                        for b in self.ladder()],
            "short_block": self.blocks[0],
            "experts_held": latent_moe.experts_held(cfg),
            "expert_offset": cfg.expert_offset,
            "n_routed_experts": cfg.n_routed_experts,
            "vocab": cfg.vocab_size, "max_len": cfg.max_len,
            "weight_dtype": cfg.weight_dtype,
        }

    def session_state(self, key: str, layer: int) -> Optional[tuple]:
        """What an ``"S"``, ``"C"`` or ``"W"`` layer keeps for a session the
        table holds: ``(the tokens its state stands at, {name: its slot's
        row, a ring's rows})``;
        ``None`` for a session that is not held or whose block is still being
        cut. Waits for the dispatch under way. (For tests and for a comparison of the
        served state with a reference's: nothing on the serve path reads
        it.)"""
        with self._lock:
            sess = self._sessions.get(key)
            if sess is None or not sess.slot or key in self._cutting:
                return None
            return sess.tokens.copy(), {
                name: np.asarray(rows[sess.slot])
                for name, rows in self.cache[layer].items()}

    def close(self) -> None:
        """Gives the device back: the cache, the counters, the executables
        and this object's hold on the weights."""
        self._finalizer()
        with self._lock:
            self.cache = self.counters = self.tok_cache = self.params = None
            self._exe.clear()
            self._shared.clear()
            self._sessions.clear()
