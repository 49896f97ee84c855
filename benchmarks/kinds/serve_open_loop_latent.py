"""Kind `serve_open_loop_latent`: `serve_open_loop` for a family whose
requests keep LATENT rows in pages and no K/V, served above the knee.
The run is the same (`measure` is inherited: the family's `kv_snapshot`
copies whatever one live slot keeps at the window's close); the
comparison with the reference holds that slot's latent rows and rotary
keys in every attention block, and counts the positions at which the
program's router chose another set of experts than the reference's.

Above the knee the queue grows all through the run, so the tails say how
long the run was and not how good the server is: every end-to-end number
goes on an earlier line (``end_to_end_all``) and the cell reports tokens
per second and set-up alone.

The control (`control=True`) is the same run with the reference's own
latent rows and rotary keys, rounded to float8 (e4m3: the nearest stored
precision below the bfloat16 the configuration states), standing in the
slot's place in the comparison: what rows kept one step coarser would
read. The program has no such path of its own.
"""

from benchmarks.kinds import serve_open_loop


class Runner(serve_open_loop.Runner):
    def measure(self, seed, seconds, trace_dir=None):
        res = super().measure(seed, seconds, trace_dir)
        res["info"]["end_to_end_all"] = dict(res["end_to_end"])
        return res

    @staticmethod
    def _kept(gaps):
        return dict(
            latent_gap_first_block=gaps["latent"][0],
            latent_gap_worst_block=max(gaps["latent"]),
            rope_key_gap_worst_block=max(gaps["rope"]),
        )

    def check(self):
        """The served tokens of a seeded sample of requests against the
        reference's logits (as `serve_open_loop`), and what one live
        slot kept at the window's close against the reference's forward
        over the same tokens: per attention block the latent rows and
        the rotary keys, and the share of (position, layer) pairs whose
        chosen experts differ."""
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
            gaps = self.family.reference_latent_gaps(
                self.config, last["seed"], tokens, snap,
                lowered=self.family.CONTROL_LATENT if self.control else None)
            rows = snap["rows"]
            pairs = rows * len(gaps["routing_differs"])
            differs = sum(gaps["routing_differs"])
            values.update(
                self._kept(gaps), routing_differs_share=differs / pairs)
            detail.update(
                rows_checked=rows,
                routing_differs=f"{differs} of {pairs} (position, layer) pairs",
                routing_differs_by_layer=gaps["routing_differs"],
                reference_margin_where_differs_max=max(
                    gaps["margin_where_differs"]),
                latent_gap_by_block=[round(g, 5) for g in gaps["latent"]],
                rope_key_gap_by_block=[round(g, 5) for g in gaps["rope"]],
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
