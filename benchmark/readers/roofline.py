"""Bytes a kernel has to move, from shapes alone (nothing an
implementation chooses), for its share of the HBM roofline.

Widths are the columnar schema's (``zipkin_tpu/columnar/schema.py``
docstring): a span row is 3 i64 ids + 2 i32 ids + 7 i64 times + 1 u8
flags = 89 B; an annotation row i32 + i64 + 3 i32 = 24 B; a binary
annotation row 5 i32 + 1 u8 = 21 B. An index-arena row is 3 i64 = 24 B.
"""

SPAN_ROW_B, ANN_ROW_B, BANN_ROW_B, INDEX_ROW_B = 89, 24, 21, 24


def index_rows(spans: int, anns: int, banns: int, services_per_span: int,
               indexed_anns: int) -> int:
    """Index-arena rows one launch has to touch: per span and service
    name one service row and one service+span-name row; one row per
    non-core annotation; two per binary annotation (key, key=value);
    one trace-membership row per span, annotation and binary row."""
    return (spans * services_per_span * 2 + indexed_anns + banns * 2
            + spans + anns + banns)


def ingest_step(traffic: dict) -> float:
    """Least bytes of one fused ingest step of one ``Log`` call: the
    batch's columns read, the same rows written to the rings, and each
    index row it touches read and written once. They are the bytes of
    the whole call wherever its rows land: a launch that spreads the
    call's spans over n chips moves these bytes once among them, not
    once a chip, and has n chips' bandwidth to do it with."""
    s = traffic["call_spans"]
    a = s * traffic["annotations_per_span"]
    b = s * traffic["binary_per_span"]
    rows = index_rows(s, a, b, traffic["services_per_span"],
                      s * traffic["indexed_annotations_per_span"])
    batch = s * SPAN_ROW_B + a * ANN_ROW_B + b * BANN_ROW_B
    return float(2 * batch + 2 * INDEX_ROW_B * rows)
