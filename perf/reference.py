"""The plain reference: Duke's pair score, written from its semantics.

It imports nothing of the program.  It reads the configuration's
service XML itself (``parse_service``): for each property its record
column, that column's cleaner if it has one (a module under
``perf/cleaners/``), its comparator (a module under
``perf/comparators/``) and its low and high probabilities, and the
schema's threshold.  The score is Duke's (Processor.compare,
PropertyImpl):

    p = (high - 0.5) * sim**2 + 0.5   if sim >= 0.5 else low
    prob = 0.5, folded with every property's p by
    bayes(a, b) = a*b / (a*b + (1-a)*(1-b))

in the order the schema lists the properties; a property with no value
on either side, before or after cleaning, is skipped.  ``dtype`` is
numpy's float64 for the reference and float32 for the control.
"""

from __future__ import annotations

import math
import xml.etree.ElementTree as ET

import numpy as np

import plugins


def _file_name(text: str) -> str:
    """The file a comparator or cleaner named in the XML is read from."""
    return text.strip().lower()


def parse_service(xml: str) -> dict:
    """The one workload of a configuration's service XML: its kind, name,
    link mode, datasets in group order, threshold, and for each property
    the record column it reads, that column's cleaner (None without one),
    its comparator and its probabilities.  Every data source has to feed
    a property from the same column through the same cleaner."""
    root = ET.fromstring(xml)
    (wl,) = [e for e in root if e.tag in ("Deduplication", "RecordLinkage")]
    duke = wl.find("duke")
    columns, datasets = {}, []
    for src in duke.iter("data-source"):
        for param in src.findall("param"):
            if param.get("name") == "dataset-id":
                datasets.append(param.get("value"))
        for col in src.findall("column"):
            cleaner = col.get("cleaner")
            fed = (col.get("name"), cleaner and _file_name(cleaner))
            prop = col.get("property")
            if columns.setdefault(prop, fed) != fed:
                raise ValueError(
                    f"property {prop!r} is fed as {columns[prop]} and as "
                    f"{fed} (column, cleaner): the reference scores one")
    schema = duke.find("schema")
    props = [{"column": columns[p.findtext("name")][0],
              "cleaner": columns[p.findtext("name")][1],
              "comparator": _file_name(p.findtext("comparator")),
              "low": float(p.findtext("low")),
              "high": float(p.findtext("high"))}
             for p in schema.findall("property")]
    return {
        "kind": ("deduplication" if wl.tag == "Deduplication"
                 else "recordlinkage"),
        "name": wl.get("name"),
        "link_mode": wl.get("link-mode", "many-to-many"),
        "datasets": datasets,
        "threshold": float(schema.findtext("threshold")),
        "properties": props,
    }


def _cleaned(value, clean):
    """Duke's value after its column's cleaner; None or empty drops it."""
    return clean(value) if value and clean else value


class Schema:
    def __init__(self, service: dict):
        self.threshold = service["threshold"]
        self.props = [
            (p["column"],
             p["cleaner"] and plugins.load("cleaners", p["cleaner"]).clean,
             plugins.load("comparators", p["comparator"]), p["low"],
             p["high"]) for p in service["properties"]]

    def score(self, r1: dict, r2: dict, dtype=np.float64) -> float:
        f = dtype
        half, one = f(0.5), f(1.0)
        prob = half
        for column, clean, comp, low, high in self.props:
            v1 = _cleaned(r1.get(column), clean)
            v2 = _cleaned(r2.get(column), clean)
            if not v1 or not v2:
                continue
            sim = f(comp.compare(v1, v2))
            p = (f(high) - half) * (sim * sim) + half if sim >= half \
                else f(low)
            num = prob * p
            prob = num / (num + (one - prob) * (one - p))
        return float(prob)

    def logit_bound(self) -> float:
        """Duke's threshold as a log-odds sum."""
        t = self.threshold
        return math.log(t / (1.0 - t))


class Corpus:
    """Records as of the end of a run, scanned a query at a time.

    The scan is exact: comparators that offer a vectorized ``Column``
    give every row's similarity, or an upper bound of it; for the others
    each row is bounded by the property's ``high``.  A property's
    probability never falls as its similarity rises, so a row whose
    bounded log-odds sum cannot pass the threshold cannot link; every
    other row is scored in full by ``Schema.score``."""

    def __init__(self, schema: Schema, keys, records):
        self.schema = schema
        self.keys = list(keys)
        self.records = list(records)
        self.columns = []
        for column, clean, comp, low, high in schema.props:
            vals = [_cleaned(r.get(column), clean) or ""
                    for r in self.records]
            vec = getattr(comp, "Column", None)
            self.columns.append(vec(vals) if vec else None)

    def matches(self, query: dict, margin: float, dtype=np.float64):
        """{row key: score} of every row scoring above threshold + margin."""
        bound = np.zeros(len(self.records))
        for (column, clean, comp, low, high), col in zip(self.schema.props,
                                                         self.columns):
            v = _cleaned(query.get(column), clean)
            if not v:
                continue
            hi = math.log(high / (1.0 - high))
            if col is None:
                bound += hi
                continue
            sim = col.similarity(v)
            p = np.where(sim >= 0.5, (high - 0.5) * sim * sim + 0.5, low)
            p = np.clip(p, 1e-12, 1 - 1e-12)
            bound += np.where(col.empty, 0.0, np.log(p / (1.0 - p)))
        # 1e-9 of slack keeps rounding in the bound from dropping a row
        rows = np.nonzero(bound > self.schema.logit_bound() - 1e-9)[0]
        out = {}
        thr = self.schema.threshold + margin
        for i in rows:
            s = self.schema.score(query, self.records[i], dtype)
            if s > thr:
                out[self.keys[i]] = s
        return out
