"""Kind `serve_open_loop_windowed`: `serve_open_loop` for a family whose
window layers' pages go back to the allocator while a request runs,
served above the knee. The run is the same (`measure` is inherited: the
family's `kv_snapshot` copies whatever one live slot keeps at the
window's close); the comparison with the reference holds, for the served
tokens, a sample that includes the two requests with the longest
contexts (past the window by more than a thousand positions where the
traffic has such), and for the slot its K and V in every layer, a window
layer's over the pages that are still live, and counts the positions at
which the program's router chose another set of experts than the
reference's. `kv_gap_first_layer` is the first global and the first
window layer's (rotated K against the reference's rotated K), over the
positions no earlier layer's changed choice has reached: what precision
alone costs; `kv_gap_worst_layer` is every layer's over all positions.

Above the knee the queue grows all through the run, so the tails say how
long the run was and not how good the server is: every end-to-end number
goes on an earlier line (``end_to_end_all``) and the cell reports tokens
per second and set-up alone.

The control (`control=True`) is the same run with the reference's own K
and V, rounded to float8 (e4m3: the nearest stored precision below the
bfloat16 the configuration states), standing in the slot's place in the
comparison: what rows kept one step coarser would read. The program has
no such path of its own (`kv_dtype=int8` is refused for this model).
"""

import numpy as np

from benchmarks.kinds import serve_open_loop


class Runner(serve_open_loop.Runner):
    def measure(self, seed, seconds, trace_dir=None):
        res = super().measure(seed, seconds, trace_dir)
        res["info"]["end_to_end_all"] = dict(res["end_to_end"])
        return res

    def _sample(self, plan, served, seed):
        """The requests whose tokens are compared: the two with the
        longest contexts, the rest drawn from the seed."""
        k = min(int(self.mix["check"]["sample_requests"]), len(served))
        by_context = sorted(
            served, reverse=True,
            key=lambda i: len(plan.prompts[i]) + int(plan.max_new[i]))
        longest, rest = by_context[:2], by_context[2:]
        rng = np.random.default_rng(int(seed))
        drawn = rng.choice(rest, size=min(max(k - 2, 0), len(rest)),
                           replace=False) if rest else []
        return longest + [int(i) for i in drawn]

    def check(self):
        last = self._last
        plan, results, served = last["plan"], last["results"], last["served"]
        limits = self.mix["check"]["limits"]
        values, detail = {}, {}
        if served:
            sample = self._sample(plan, served, last["seed"])
            # the base takes the longest and draws the rest of the
            # sample's size from what it is given: all of it
            values, detail = self._token_gaps(
                plan, results, sample, last["seed"])
            detail["checked_contexts"] = sorted(
                len(plan.prompts[i]) + int(plan.max_new[i]) for i in sample)
        snap = last["snapshot"]
        r = results.get(snap["request_id"]) if snap else None
        if r is not None:
            tokens = list(r.prompt) + list(r.tokens)
            family = self.family
            gaps = family.reference_kept_gaps(
                self.config, last["seed"], tokens, snap,
                lowered=family.CONTROL_KV if self.control else None)
            kinds = family.layer_types(self.config)
            # the first layer of each kind, over the positions no
            # earlier layer's changed choice of experts has reached:
            # precision alone (layer 0 holds no earlier choice at all)
            first = [kinds.index(k) for k in ("global", "window")]
            rows = snap["rows"]
            pairs = rows * len(kinds)
            differs = sum(gaps["routing_differs"])
            values.update(
                kv_gap_first_layer=max(
                    max(gaps["k_clean"][i], gaps["v_clean"][i])
                    for i in first),
                kv_gap_worst_layer=max(gaps["k"] + gaps["v"]),
                routing_differs_share=differs / pairs)
            detail.update(
                rows_checked=rows, first_live_window_row=snap["first_live"],
                snapshot_past_its_window=bool(snap["first_live"] > 0),
                routing_differs=f"{differs} of {pairs} (position, layer) pairs",
                routing_differs_by_layer=gaps["routing_differs"],
                reference_margin_where_differs_max=max(
                    gaps["margin_where_differs"]),
                k_gap_by_layer=[round(g, 5) for g in gaps["k"]],
                v_gap_by_layer=[round(g, 5) for g in gaps["v"]],
                k_gap_clean_by_layer=[round(g, 5) for g in gaps["k_clean"]],
                v_gap_clean_by_layer=[round(g, 5) for g in gaps["v_clean"]],
            )
        last["snapshot"] = None  # the copied rows go back to the device
        comparisons = [
            {"name": k, "value": values.get(k), "limit": float(limits[k])}
            for k in limits
        ]
        correct = all(
            c["value"] is not None and c["value"] <= c["limit"]
            for c in comparisons)
        return correct, comparisons, detail
