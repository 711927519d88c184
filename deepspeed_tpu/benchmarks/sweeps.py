"""Newest-recorded-sweep discovery for the collective sweep's regression
gate (the COMMBENCH convention): find the most recent JSON report in a
directory whose ``{"n": device_count, "rows": [...]}`` document matches the
current topology — sweeps from a different device count are skipped, their
numbers aren't comparable."""

from __future__ import annotations

import glob
import json
import os
from typing import Dict, List, Optional, Sequence, Tuple


def latest_recorded_sweep(baseline_dir: str, patterns: Sequence[str],
                          n_devices: Optional[int] = None
                          ) -> Tuple[Optional[str], List[Dict]]:
    """(basename, rows) of the newest parseable report under
    ``baseline_dir`` matching any of ``patterns`` (newest mtime first);
    unreadable/row-less docs and other-device-count sweeps are
    skipped."""
    paths = sorted(
        (p for pat in patterns
         for p in glob.glob(os.path.join(baseline_dir, pat))),
        key=os.path.getmtime, reverse=True)
    for path in paths:
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        rows = doc.get("rows") if isinstance(doc, dict) else None
        if not rows:
            continue
        if n_devices is not None and doc.get("n") is not None and \
                int(doc["n"]) != int(n_devices):
            continue
        return os.path.basename(path), rows
    return None, []
