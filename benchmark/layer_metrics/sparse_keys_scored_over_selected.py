"""Model: keys whose scores a block-sparse model's attention computed over
keys its selection chose, summed over the traced ``decode_block`` and
``prefill_chunk`` spans (the model's counters ``sparse_keys_scored`` and
``sparse_keys_selected``: for each real query, sparse layer and key/value
group). 1.0 is the floor: the attention read what the selection chose and no
more. A decode step that gathers the selected blocks reads 1.01; a chunk
computed densely over the whole row and masked reads the row's length over
some 4000. Nothing to read where the program writes no such counters."""

from benchmark import span_reduce


def read(run: dict):
    fields = [e["fields"] for span in ("decode_block", "prefill_chunk")
              for e in span_reduce.events_of(run, span)
              if e["fields"].get("sparse_keys_selected")]
    if not fields:
        return None
    return (sum(f["sparse_keys_scored"] for f in fields)
            / sum(f["sparse_keys_selected"] for f in fields))
