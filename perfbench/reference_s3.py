"""The plain reference of an S3 bucket, and a SigV4 signer of its own.

What the S3 API promises about four operations and the listing, kept as a
dict: key -> (size, SHA-256, MD5) of the last body a PUT of that key was
acknowledged with.  The same operations on the same bodies give the same
answers from a gateway that keeps its promises: `PUT` 200 with `ETag` the
body's MD5 in hex, quoted; `GET` of a live key 200 and the bytes (here:
their size and SHA-256); `HEAD` 200 with `Content-Length` and `ETag`;
`DELETE` 204, of an absent key too; `GET` / `HEAD` of a deleted or absent
key 404; ListObjectsV2 the live keys in byte order.

Beside it, AWS Signature Version 4 for header authentication, with
`x-amz-content-sha256` the body's SHA-256, written from the published
algorithm (docs.aws.amazon.com, "Signature Version 4 signing process"):
the gateway's `s3api/auth.py` is checked against a second implementation
with every request.  Nothing here imports the program.
"""

from __future__ import annotations

import hashlib
import hmac
import time
import urllib.parse
import xml.etree.ElementTree as ET
from typing import NamedTuple, Optional

EMPTY_SHA256 = hashlib.sha256(b"").hexdigest()
S3_NS = "{http://s3.amazonaws.com/doc/2006-03-01/}"


class Answer(NamedTuple):
    """What an S3 operation answers, as far as a client can compare it:
    `sha256` stands for the body's bytes."""
    status: int
    size: Optional[int] = None
    etag: Optional[str] = None
    sha256: Optional[bytes] = None


class Stored(NamedTuple):
    size: int
    sha256: bytes
    md5: str


def describe(body, sha256: Optional[bytes] = None) -> Stored:
    """Size, SHA-256 and MD5 of a body (the SHA-256 may be handed in by a
    client that computed it to sign the request)."""
    return Stored(len(body),
                  sha256 or hashlib.sha256(body).digest(),
                  hashlib.md5(body).hexdigest())


class Bucket:
    """One bucket.  Not thread-safe: the caller serialises operations on
    one key, as S3 itself gives no order to concurrent writers."""

    def __init__(self):
        self.objects: dict[str, Stored] = {}
        self.deleted: dict[str, int] = {}   # key -> payload bytes deleted

    def put(self, key: str, stored: Stored) -> Answer:
        self.objects[key] = stored
        self.deleted.pop(key, None)
        return Answer(200, etag=f'"{stored.md5}"')

    def get(self, key: str) -> Answer:
        o = self.objects.get(key)
        if o is None:
            return Answer(404)
        return Answer(200, o.size, f'"{o.md5}"', o.sha256)

    def head(self, key: str) -> Answer:
        o = self.objects.get(key)
        if o is None:
            return Answer(404)
        return Answer(200, o.size, f'"{o.md5}"')

    def delete(self, key: str) -> Answer:
        o = self.objects.pop(key, None)
        if o is not None:
            self.deleted[key] = self.deleted.get(key, 0) + o.size
        return Answer(204)

    def list(self) -> list[tuple[str, int, str]]:
        """ListObjectsV2 without prefix or delimiter: (key, size, etag)
        of every live key, in the byte order of the keys."""
        return [(k, o.size, f'"{o.md5}"')
                for k, o in sorted(self.objects.items(),
                                   key=lambda kv: kv[0].encode())]

    def deleted_bytes(self) -> int:
        return sum(self.deleted.values())


def parse_listing(xml: bytes) -> tuple[list[tuple[str, int, str]], str]:
    """One ListObjectsV2 page: its (key, size, etag) rows and the
    continuation token, "" on the last page."""
    root = ET.fromstring(xml)
    rows = [(c.findtext(f"{S3_NS}Key"), int(c.findtext(f"{S3_NS}Size")),
             c.findtext(f"{S3_NS}ETag"))
            for c in root.iter(f"{S3_NS}Contents")]
    more = root.findtext(f"{S3_NS}IsTruncated") == "true"
    token = root.findtext(f"{S3_NS}NextContinuationToken") or ""
    return rows, token if more else ""


# -- Signature Version 4 -----------------------------------------------------------

def _hmac(key: bytes, msg: str) -> bytes:
    return hmac.new(key, msg.encode(), hashlib.sha256).digest()


def signing_key(secret_key: str, datestamp: str, region: str,
                service: str = "s3") -> bytes:
    k = _hmac(("AWS4" + secret_key).encode(), datestamp)
    for part in (region, service, "aws4_request"):
        k = _hmac(k, part)
    return k


def quote(value: str, safe: str = "") -> str:
    return urllib.parse.quote(value, safe=safe + "-_.~")


def canonical_request(method: str, path: str, query: dict, headers: dict,
                      payload_hash: str) -> tuple[str, str]:
    """The canonical request and its SignedHeaders, over every header
    handed in (names lowered, values trimmed, sorted by name)."""
    lowered = sorted((k.lower(), " ".join(str(v).split()))
                     for k, v in headers.items())
    signed = ";".join(k for k, _ in lowered)
    pairs = sorted((quote(k), quote(str(v))) for k, v in query.items())
    return "\n".join([
        method, quote(path, "/"),
        "&".join(f"{k}={v}" for k, v in pairs),
        "".join(f"{k}:{v}\n" for k, v in lowered),
        signed, payload_hash]), signed


def sign(method: str, host: str, path: str, query: Optional[dict],
         payload_hash: str, access_key: str, secret_key: str,
         now: Optional[float] = None, region: str = "us-east-1") -> dict:
    """The headers of one signed request: Host, X-Amz-Date,
    X-Amz-Content-Sha256 (the body's SHA-256 in hex, which the caller
    computed) and Authorization over the three."""
    t = time.gmtime(time.time() if now is None else now)
    amz_date = time.strftime("%Y%m%dT%H%M%SZ", t)
    datestamp = amz_date[:8]
    headers = {"Host": host, "X-Amz-Date": amz_date,
               "X-Amz-Content-Sha256": payload_hash}
    canonical, signed = canonical_request(method, path, query or {},
                                          headers, payload_hash)
    scope = f"{datestamp}/{region}/s3/aws4_request"
    to_sign = "\n".join(["AWS4-HMAC-SHA256", amz_date, scope,
                         hashlib.sha256(canonical.encode()).hexdigest()])
    signature = hmac.new(signing_key(secret_key, datestamp, region),
                         to_sign.encode(), hashlib.sha256).hexdigest()
    headers["Authorization"] = (
        f"AWS4-HMAC-SHA256 Credential={access_key}/{scope}, "
        f"SignedHeaders={signed}, Signature={signature}")
    return headers
