"""Chunks the chunked traversal dispatched over the chunks of the tile
schedule, summed over the window's requests (per-request stats)."""


def read(run):
    st = [r["stats"] for r in run["records"]
          if r is not None and "n_chunks" in r["stats"]]
    total = sum(s["n_chunks"] for s in st)
    return (100.0 * sum(s["chunks_dispatched"] for s in st) / total
            if total else None)
