"""Kind `serve_open_loop_state`: `serve_open_loop` for a family whose
requests keep more than K/V pages. The run is the same (`measure` is
inherited: the family's `kv_snapshot` copies whatever one live slot
keeps at the window's close); the comparison with the reference also
holds that slot's recurrent state and convolution tail in every layer
that has them, and counts the positions at which the program's router
chose another set of experts than the reference's.

Against the float32 reference a kept state reads what the bfloat16
activations that feed it cost, about 1%, and a state stored in bfloat16
adds too little to that for any gap to tell the two apart (PERF.md, PR
26: measured twice, the second time against a replay at the stated
precision, which XLA's excess precision defeats on the chip). What does
tell them apart is the state itself: ``state_coarse_share_worst_layer``
is the share of its non-zero elements that 15 bits of mantissa hold
exactly (`family._coarse_share`), 2**-8 for a state kept and advanced in
float32 and 1 for one that has passed through bfloat16.

The control (`control=True`) is the engine's own lower-precision path,
the recurrent state stored in bfloat16, run and compared as ever. The
reference itself computed in bfloat16 throughout
(`family.lowered_snapshot`), standing in the slot's place, is read beside
it (``lowered_reference`` in the detail).
"""

from benchmarks.kinds import serve_open_loop


def _worst(values):
    values = [v for v in values if v is not None]
    return max(values) if values else None


def _first(values):
    return next((v for v in values if v is not None), None)


class Runner(serve_open_loop.Runner):
    @staticmethod
    def _kept(gaps):
        return dict(
            kv_gap_worst_layer=_worst(gaps["k"] + gaps["v"]),
            state_gap_first_layer=_first(gaps["state"]),
            state_gap_worst_layer=_worst(gaps["state"]),
            state_coarse_share_worst_layer=_worst(gaps["coarse"]),
            conv_tail_gap_worst_layer=_worst(gaps["tail"]),
        )

    def measure(self, seed, seconds, trace_dir=None):
        res = super().measure(seed, seconds, trace_dir)
        # every end-to-end number of the run on an earlier line, those
        # the cell does not report among them (PERF.md says why not)
        res["info"]["end_to_end_all"] = dict(res["end_to_end"])
        return res

    def check(self):
        """The served tokens of a seeded sample of requests against the
        reference's logits (as `serve_open_loop`), and what one live
        slot kept at the window's close against the reference's forward
        over the same tokens, layer by layer: K and V, state, tail, the
        share of (position, layer) pairs whose chosen experts differ,
        and how coarse a grid the kept state lies on."""
        last = self._last
        plan, results, served = last["plan"], last["results"], last["served"]
        limits = self.mix["check"]["limits"]
        values, detail = {}, {}
        if served:
            values, detail = self._token_gaps(
                plan, results, served, last["seed"])
        snap = last["snapshot"]
        r = results.get(snap["request_id"]) if snap else None
        if r is not None:
            tokens = list(r.prompt) + list(r.tokens)
            gaps = self.family.reference_state_gaps(
                self.config, last["seed"], tokens, snap)
            rows = snap["rows"]
            pairs = rows * len(gaps["routing_differs"])
            differs = sum(gaps["routing_differs"])
            values.update(self._kept(gaps), routing_differs_share=differs / pairs)
            detail.update(
                rows_checked=rows,
                routing_differs=f"{int(differs)} of {pairs} (position, layer) pairs",
                routing_differs_by_layer=[int(d) for d in gaps["routing_differs"]],
                reference_margin_where_differs_max=round(
                    max(gaps["margin_where_differs"]), 5),
                **{
                    f"{name}_gap_by_layer": [
                        None if g is None else round(g, 5) for g in gaps[name]]
                    for name in ("k", "v", "state", "tail")
                },
            )
            if self.control:
                low = self.family.lowered_snapshot(
                    self.config, last["seed"], tokens, snap)
                detail["lowered_reference"] = self._kept(
                    self.family.reference_state_gaps(
                        self.config, last["seed"], tokens, low))
        last["snapshot"] = None  # the copied rows go back to the device
        comparisons = [
            {"name": k, "value": values.get(k), "limit": float(limits[k])}
            for k in limits
        ]
        correct = all(
            c["value"] is not None and c["value"] <= c["limit"]
            for c in comparisons)
        return correct, comparisons, detail
