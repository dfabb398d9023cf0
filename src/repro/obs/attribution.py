"""Per-step overhead attribution and causal-link validation.

The paper's Fig. 9 decomposes per-task cost into thread management,
parcel handling, and AGAS operations.  ``attribute`` applies the same
analysis online to serving traces: every engine ``step`` span is a
root; each span in its tree contributes its *self time* (duration minus
direct children) to the category named by its ``kind``:

- ``compute``  kernel work (prefill/chunk/decode dispatch)
- ``sched``    scheduling: admit bookkeeping, rebalance planning
- ``pages``    page accounting: attach/alloc/COW/write staging
- ``parcel``   parcel staging: migration plans, percolation prefetch
- ``copy``     host<->device copies (demote/promote/offload)
- ``other``    uncategorized runtime glue (incl. step self time)

Self times of a tree sum to the root's duration by construction, so
compute + overhead always reconciles with step wall-clock (the
``sum_residual`` in the report is float noise).  Overhead is everything
that is not ``compute``.

``check_nesting`` / ``check_causal`` validate the trace itself: child
intervals nest within parents, and every causal id resolves — rids
trace back to a submit, slot references fall inside a bind interval,
page gids are referenced only within their alloc..free lifetime (gids
are never recycled, so this is decidable).  Causal validation assumes a
complete trace: check ``tracer.dropped == 0`` before trusting it.
"""

CATEGORIES = ("compute", "sched", "pages", "parcel", "copy", "other")
ROLES = ("prefill", "decode", "handoff", "other")
_EPS = 1e-9

__all__ = ["CATEGORIES", "ROLES", "attribute", "attribute_roles",
           "check_nesting", "check_causal", "subsystems"]


def subsystems(records):
    return {r.subsystem for r in records}


def attribute(records, root_subsystem="engine", root_name="step"):
    """Decompose step wall-clock into per-category self times."""
    spans = [r for r in records if r.dur is not None]
    children = {}
    for s in spans:
        if s.parent is not None:
            children.setdefault(s.parent, []).append(s)
    steps = [s for s in spans
             if s.subsystem == root_subsystem and s.name == root_name]
    cat = {c: 0.0 for c in CATEGORIES}
    wall = 0.0
    for step in steps:
        wall += step.dur
        stack = [step]
        while stack:
            s = stack.pop()
            kids = children.get(s.sid, ())
            self_t = s.dur - sum(k.dur for k in kids)
            if self_t < 0.0:
                self_t = 0.0
            key = s.kind if s.kind in cat else "other"
            cat[key] += self_t
            stack.extend(kids)
    total = sum(cat.values())
    compute = cat["compute"]
    overhead = total - compute
    return {
        "steps": len(steps),
        "wall_ms": wall * 1e3,
        "compute_ms": compute * 1e3,
        "overhead_ms": overhead * 1e3,
        "compute_fraction": compute / wall if wall else 0.0,
        "overhead_fraction": overhead / wall if wall else 0.0,
        "categories_ms": {c: v * 1e3 for c, v in cat.items()},
        "sum_residual": abs(total - wall) / wall if wall else 0.0,
    }


_ROLE_BY_NAME = {
    "prefill": "prefill",
    "prefill_chunk": "prefill",
    "resume": "prefill",
    "decode_batch": "decode",
    "handoff_stage": "handoff",
    "handoff_commit": "handoff",
}


def span_role(span, parent_role="other"):
    """Disagg role a span's self time belongs to.

    Prefill execution (whole-prompt, chunked, compute-skip resume)
    is prefill-worker work; the decode batch is decode-worker work;
    percolation handoff stage/commit is the copy seam between them.
    An engine span inside one of those (the decode batch's inputs,
    dispatch and sampling; a chunk's inputs and first token; a fetch)
    is its enclosing role's work.  Everything else (admit
    bookkeeping, page accounting, tier traffic, step glue) is
    role-neutral runtime -> ``other``.
    """
    role = _ROLE_BY_NAME.get(span.name)
    if role is not None:
        return role
    return parent_role if span.subsystem == "engine" else "other"


def span_locality(span):
    """AGAS locality a span executed against, or None."""
    loc = span.args.get("loc")
    return loc


def attribute_roles(records, root_subsystem="engine", root_name="step"):
    """Fig. 9 buckets split by disagg role and AGAS locality.

    Same self-time tree walk as ``attribute`` — self times sum to
    step wall by construction — but each span's self time lands in
    (a) the role bucket named by the span (prefill worker vs decode
    worker vs handoff copy vs role-neutral runtime; ``span_role``) and
    (b) the locality bucket from the span's ``loc`` arg, which engine
    spans inherit from their parent (spans without one aggregate
    under ``"engine"``).  Under ``--disagg --kv-shards N``
    this proves *where* overhead lives: which role pays it, and on
    which locality's pool it runs.
    """
    spans = [r for r in records if r.dur is not None]
    children = {}
    for s in spans:
        if s.parent is not None:
            children.setdefault(s.parent, []).append(s)
    steps = [s for s in spans
             if s.subsystem == root_subsystem and s.name == root_name]
    roles = {r: 0.0 for r in ROLES}
    locs = {}
    wall = 0.0
    for step in steps:
        wall += step.dur
        stack = [(step, "other", None)]
        while stack:
            s, prole, ploc = stack.pop()
            kids = children.get(s.sid, ())
            self_t = s.dur - sum(k.dur for k in kids)
            if self_t < 0.0:
                self_t = 0.0
            role = span_role(s, prole)
            roles[role] += self_t
            loc = span_locality(s)
            if loc is None and s.subsystem == "engine":
                loc = ploc
            lkey = "engine" if loc is None else f"loc{loc}"
            locs[lkey] = locs.get(lkey, 0.0) + self_t
            stack.extend((k, role, loc) for k in kids)
    total = sum(roles.values())
    return {
        "steps": len(steps),
        "wall_ms": wall * 1e3,
        "roles_ms": {r: v * 1e3 for r, v in roles.items()},
        "localities_ms": {k: v * 1e3
                          for k, v in sorted(locs.items())},
        "sum_residual": abs(total - wall) / wall if wall else 0.0,
    }


def check_nesting(records):
    """Every child interval must nest within its recorded parent."""
    spans = {r.sid: r for r in records if r.dur is not None}
    problems = []
    for r in records:
        if r.parent is None:
            continue
        p = spans.get(r.parent)
        if p is None:
            continue  # parent evicted from the ring or still open
        end = r.t0 if r.dur is None else r.t0 + r.dur
        if r.t0 < p.t0 - _EPS or end > p.t0 + p.dur + _EPS:
            problems.append(
                f"{r.subsystem}/{r.name} sid={r.sid} "
                f"[{r.t0:.9f}, {end:.9f}] escapes parent "
                f"{p.subsystem}/{p.name} sid={p.sid} "
                f"[{p.t0:.9f}, {p.t0 + p.dur:.9f}]")
    return problems


def _ref_gids(r):
    gids = r.args.get("gids")
    if gids is not None:
        return gids
    g = r.args.get("gid")
    return () if g is None else (g,)


def check_causal(records):
    """request -> slot -> page links: nothing may dangle."""
    problems = []
    submitted = set()
    binds = {}    # slot -> [(t, rid), ...] in time order
    alloc_t = {}  # gid -> t   (gids never recycled)
    free_t = {}   # gid -> t
    events = sorted(records, key=lambda r: (r.t0, r.sid))
    for r in events:
        if r.subsystem == "engine":
            if r.name == "submit":
                submitted.add(r.args.get("rid"))
            elif r.name == "slot_bind":
                binds.setdefault(r.args.get("slot"), []).append(
                    (r.t0, r.args.get("rid")))
        elif r.subsystem == "kvcache":
            if r.name == "page_alloc":
                alloc_t[r.args.get("gid")] = r.t0
            elif r.name == "page_free":
                free_t[r.args.get("gid")] = r.t0
    for r in events:
        end = r.t0 if r.dur is None else r.t0 + r.dur
        rid = r.args.get("rid")
        if rid is not None and not (r.subsystem == "engine"
                                    and r.name == "submit"):
            if rid not in submitted:
                problems.append(
                    f"{r.subsystem}/{r.name}: rid {rid!r} never "
                    f"submitted")
        slot = r.args.get("slot")
        if slot is not None and r.subsystem == "kvcache":
            live = [b for b in binds.get(slot, []) if b[0] <= end + _EPS]
            if not live:
                problems.append(
                    f"{r.subsystem}/{r.name}: slot {slot} used before "
                    f"any bind")
            elif live[-1][1] not in submitted:
                problems.append(
                    f"{r.subsystem}/{r.name}: slot {slot} bound to "
                    f"unsubmitted rid {live[-1][1]!r}")
        if r.subsystem == "kvcache" and r.name in ("page_alloc",
                                                   "page_free"):
            continue
        for g in _ref_gids(r):
            at = alloc_t.get(g)
            if at is None:
                problems.append(
                    f"{r.subsystem}/{r.name}: gid {g} never allocated")
                continue
            if at > end + _EPS:
                problems.append(
                    f"{r.subsystem}/{r.name}: gid {g} referenced "
                    f"before alloc")
            ft = free_t.get(g)
            if ft is not None and ft < r.t0 - _EPS:
                problems.append(
                    f"{r.subsystem}/{r.name}: gid {g} referenced "
                    f"after free")
    return problems
