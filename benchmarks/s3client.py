"""A small SigV4 S3 client of the benchmark's own (the protocol is
AWS's; nothing here is the program's).  A body's SHA-256 is handed in,
so that a request made inside the window only signs and sends."""

import datetime
import hashlib
import hmac

EMPTY_SHA256 = hashlib.sha256(b"").hexdigest()


def _hmac(key: bytes, msg: str) -> bytes:
    return hmac.new(key, msg.encode(), hashlib.sha256).digest()


class S3Client:
    def __init__(self, session, port: int, key_id: str, secret: str,
                 region: str = "garage"):
        self.session, self.port = session, port
        self.key_id, self.secret, self.region = key_id, secret, region
        self.host = f"127.0.0.1:{port}"
        self._day = None
        self._signing_key = None

    def _key_for(self, date: str) -> bytes:
        if self._day != date:
            k = _hmac(b"AWS4" + self.secret.encode(), date)
            for part in (self.region, "s3", "aws4_request"):
                k = _hmac(k, part)
            self._day, self._signing_key = date, k
        return self._signing_key

    def sign(self, method: str, path: str, payload_sha256: str) -> dict:
        """Headers of a header-authenticated request.  `path` is the
        wire form (already percent-encoded) and has no query."""
        now = datetime.datetime.now(datetime.timezone.utc).strftime(
            "%Y%m%dT%H%M%SZ")
        scope = f"{now[:8]}/{self.region}/s3/aws4_request"
        canon = "\n".join([
            method, path, "",
            f"host:{self.host}\nx-amz-content-sha256:{payload_sha256}\n"
            f"x-amz-date:{now}\n",
            "host;x-amz-content-sha256;x-amz-date", payload_sha256])
        to_sign = "\n".join([
            "AWS4-HMAC-SHA256", now, scope,
            hashlib.sha256(canon.encode()).hexdigest()])
        sig = hmac.new(self._key_for(now[:8]), to_sign.encode(),
                       hashlib.sha256).hexdigest()
        return {
            "host": self.host,
            "x-amz-date": now,
            "x-amz-content-sha256": payload_sha256,
            "Authorization": (
                f"AWS4-HMAC-SHA256 Credential={self.key_id}/{scope}, "
                "SignedHeaders=host;x-amz-content-sha256;x-amz-date, "
                f"Signature={sig}"),
        }

    async def req(self, method: str, path: str, body: bytes = b"",
                  payload_sha256: str = None):
        """→ (status, headers, body); the body is read to its last byte."""
        import yarl

        if payload_sha256 is None:
            payload_sha256 = (hashlib.sha256(body).hexdigest() if body
                              else EMPTY_SHA256)
        url = yarl.URL(f"http://{self.host}{path}", encoded=True)
        async with self.session.request(
                method, url, data=body or None,
                headers=self.sign(method, path, payload_sha256)) as r:
            return r.status, r.headers, await r.read()
