"""Seeded access-log corpus with the shapes of real web traffic.

The syntax follows the program's demolog (CLF ``-`` byte counts,
percent escapes, backslash-escaped quotes, junk from scanners and from
TLS handshakes sent to port 80); the shapes come from named sources and
every number is a parameter of the traffic file (``corpus``), listed
there under ``assumed``:

- URL and client popularity are Zipf (Breslau et al., "Web Caching and
  Zipf-like Distributions", INFOCOM 1999: alpha 0.64-0.83).
- Response sizes are heavy-tailed and the status mix is dominated by
  success (Arlitt & Williamson, "Web Server Workload Characterization",
  SIGMETRICS 1996).
- Query-string and referer-query lengths are lognormal, and some user
  agents carry app suffixes.  These parameters have no published
  source (``assumed`` says so), so no heavy tail is drawn for them; the
  request line is bounded by Apache's documented ``LimitRequestLine``
  default of 8,190 bytes.

Lines are drawn from a seeded pool of templates (everything but the
timestamp), so a file of 2**20 lines costs a few list operations, not a
Python loop per line.  Nothing here imports the program.
"""
import math
import random
from itertools import chain
from operator import itemgetter

import numpy as np

MONTHS = ("Jan", "Feb", "Mar", "Apr", "May", "Jun",
          "Jul", "Aug", "Sep", "Oct", "Nov", "Dec")
WORDS = (
    "api", "v1", "v2", "static", "img", "images", "css", "js", "assets",
    "blog", "news", "shop", "cart", "checkout", "product", "products",
    "category", "search", "user", "users", "account", "login", "logout",
    "help", "about", "contact", "docs", "download", "media", "video",
    "feed", "tag", "archive", "2024", "2025", "2026", "en", "nl", "de",
    "fr", "item", "items", "detail", "list", "page", "comments", "share",
    "wp-content", "uploads", "themes", "plugins", "fonts", "icons", "cdn",
    "caf%C3%A9", "men%C3%BC", "r%C3%A9sum%C3%A9", "hello%20world",
    "stra%C3%9Fe", "%E6%97%A5%E6%9C%AC",
)
EXTS = ("", "", "", ".html", ".php", ".js", ".css", ".png", ".jpg",
        ".gif", ".svg", ".woff2", ".json", ".xml", ".ico")
SCANNER_PATHS = (
    "/wp-login.php", "/.env", "/xmlrpc.php", "/phpmyadmin/index.php",
    "/cgi-bin/.%2e/.%2e/.%2e/bin/sh", "/admin/config.php", "/.git/config",
    "/boaform/admin/formLogin", "/HNAP1/", "/vendor/phpunit/phpunit/src/Util/PHP/eval-stdin.php",
)
QUERY_KEYS = ("id", "q", "page", "sort", "lang", "ref", "s", "cat", "tag",
              "p", "limit", "offset", "session", "v", "format", "from")
TRACKING_KEYS = ("utm_source", "utm_medium", "utm_campaign", "utm_content",
                 "gclid", "fbclid", "msclkid", "_ga", "mc_eid", "yclid")
UA_FAMILIES = (
    "Mozilla/5.0 (Windows NT 10.0; Win64; x64) AppleWebKit/537.36 (KHTML, like Gecko) Chrome/{v}.0.0.0 Safari/537.36",
    "Mozilla/5.0 (Macintosh; Intel Mac OS X 10_15_7) AppleWebKit/605.1.15 (KHTML, like Gecko) Version/{v}.1 Safari/605.1.15",
    "Mozilla/5.0 (X11; Linux x86_64; rv:{v}.0) Gecko/20100101 Firefox/{v}.0",
    "Mozilla/5.0 (iPhone; CPU iPhone OS 17_{v} like Mac OS X) AppleWebKit/605.1.15 (KHTML, like Gecko) Version/17.0 Mobile/15E148 Safari/604.1",
    "Mozilla/5.0 (Linux; Android 14; SM-S918B) AppleWebKit/537.36 (KHTML, like Gecko) Chrome/{v}.0.0.0 Mobile Safari/537.36",
    "Mozilla/5.0 (compatible; Googlebot/2.1; +http://www.google.com/bot.html)",
    "Mozilla/5.0 (compatible; bingbot/2.0; +http://www.bing.com/bingbot.htm)",
    "curl/8.{v}.0", "python-requests/2.{v}.0", "Go-http-client/1.1",
    "Wget/1.21.{v}", "facebookexternalhit/1.1 (+http://www.facebook.com/externalhit_uatext.php)",
    "Mozilla/5.0 (Windows NT 10.0; Win64; x64) AppleWebKit/537.36 (KHTML, like Gecko) Chrome/{v}.0.0.0 Safari/537.36 Edg/{v}.0.0.0",
)
STATUS_WITH_EMPTY_BODY = ("304", "204")


def _zipf_weights(n, alpha):
    w = 1.0 / np.arange(1, n + 1, dtype=np.float64) ** alpha
    return w / w.sum()


def _lengths(rng, n, spec):
    """Lognormal lengths (``median``, ``sigma``), with a Pareto tail
    where the spec gives one (a ``tail_share`` from ``tail_min``,
    ``tail_alpha``), as integers in [0, ``cap``]."""
    lens = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    if spec.get("tail_share"):
        tail = spec["tail_min"] * (1.0 - rng.random(n)) ** (
            -1.0 / spec["tail_alpha"])
        lens = np.where(rng.random(n) < spec["tail_share"], tail, lens)
    return np.minimum(lens, spec.get("cap", np.inf)).astype(np.int64)


class Chars:
    """Random URL-safe text, drawn once: a token is a slice of it."""

    ALPHABET = b"abcdefghijklmnopqrstuvwxyzABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789-_"

    def __init__(self, rng, size=1 << 22):
        alphabet = np.frombuffer(self.ALPHABET, dtype=np.uint8)
        self.text = alphabet[rng.integers(0, len(alphabet), size)].tobytes().decode()

    def token(self, r, n):
        o = int(r.random() * (len(self.text) - n))
        return self.text[o:o + n]


class Pools:
    """Everything of a deployment's traffic but the clock: URLs,
    clients, user agents and referers, each drawn once from the seed."""

    def __init__(self, p, rng, r, chars):
        self.p = p
        self.paths = self._paths(rng, r, p["urls"])
        self.clients = self._clients(r, p["clients"], p["ipv6_share"])
        self.uas = self._user_agents(r, chars, p["user_agents"])
        self.users = ["user%d" % i for i in range(1, 201)]

    @staticmethod
    def _paths(rng, r, n):
        out = []
        depth = 1 + rng.geometric(0.45, n)
        for i in range(n):
            segs = [r.choice(WORDS) for _ in range(depth[i])]
            if r.random() < 0.3:
                segs[-1] = "%s-%d" % (segs[-1], r.randrange(1, 100000))
            out.append("/" + "/".join(segs) + r.choice(EXTS))
        # The most popular URLs of a site are short: the root, the home
        # page, its favicon, its stylesheet.
        out[:4] = ["/", "/index.html", "/favicon.ico", "/static/site.css"]
        return out

    @staticmethod
    def _clients(r, n, v6_share):
        out = []
        for _ in range(n):
            if r.random() < v6_share:
                out.append("2001:db8:%x:%x::%x" % (
                    r.randrange(1, 0xffff), r.randrange(1, 0xffff),
                    r.randrange(1, 0xffff)))
            else:
                out.append("%d.%d.%d.%d" % (
                    r.randrange(1, 224), r.randrange(0, 256),
                    r.randrange(0, 256), r.randrange(1, 255)))
        return out

    @staticmethod
    def _user_agents(r, chars, n):
        out = []
        for _ in range(n):
            ua = r.choice(UA_FAMILIES).format(v=r.randrange(1, 130))
            if r.random() < 0.1:  # app and toolbar suffixes: the UA tail
                ua += " " + " ".join(
                    "%s/%d.%d" % (chars.token(r, r.randrange(3, 12)),
                                  r.randrange(1, 20), r.randrange(0, 99))
                    for _ in range(1 + int(r.expovariate(0.16))))
            if r.random() < 0.01:  # a quoted token, escaped by the server
                ua += " {q}%s{q}" % chars.token(r, 8)
            out.append(ua)
        return out


class Corpus:
    """Line templates of one dialect (``apache`` or ``nginx``) and the
    clock that completes them."""

    def __init__(self, params, seed, dialect):
        self.p = params
        self.dialect = dialect
        rng = np.random.default_rng([int(seed) & (2**63 - 1), 0x77656233])
        # Scalar draws go through Python's generator (fast per call),
        # seeded from the same stream.
        r = random.Random(int(rng.integers(2**63)))
        self.chars = Chars(rng)
        self.pools = Pools(params, rng, r, self.chars)
        self.prefixes, self.suffixes = self._templates(rng, r)
        self.lengths = np.array(
            [len(a) + len(b) + 26 for a, b in zip(self.prefixes, self.suffixes)])
        self._pre_b = self._suf_b = None

    # -- templates ------------------------------------------------------

    def _query(self, r, length):
        if length <= 0:
            return ""
        parts = []
        size = 0
        while size < length:
            key = r.choice(TRACKING_KEYS if r.random() < 0.25 else QUERY_KEYS)
            val = self.chars.token(r, max(1, min(
                length - size - len(key) - 2,
                int(r.lognormvariate(2.0, 1.0)) + 1)))
            parts.append(key + "=" + val)
            size += len(key) + len(val) + 2
        q = "&".join(parts)
        if r.random() < self.p["bad_escape_share"]:
            q += "&discount=50%-off"
        return "?" + q

    def _templates(self, rng, r):
        p, pools = self.p, self.pools
        n = p["pool_lines"]
        url_w = _zipf_weights(len(pools.paths), p["url_zipf_alpha"])
        cli_w = _zipf_weights(len(pools.clients), p["client_zipf_alpha"])
        ua_w = _zipf_weights(len(pools.uas), p["ua_zipf_alpha"])
        url_i = rng.choice(len(pools.paths), n, p=url_w)
        cli_i = rng.choice(len(pools.clients), n, p=cli_w)
        ua_i = rng.choice(len(pools.uas), n, p=ua_w)
        ref_i = rng.choice(len(pools.paths), n, p=url_w)
        ql = p["query"]
        qlen = _lengths(rng, n, ql)
        has_q = rng.random(n) < ql["share"]
        rl = p["referer_query"]
        rlen = _lengths(rng, n, rl)
        status_codes = list(p["status_mix"])
        status = rng.choice(status_codes, n,
                            p=np.array(list(p["status_mix"].values())) /
                            sum(p["status_mix"].values()))
        body = _lengths(rng, n, p["body_bytes"])
        methods = list(p["method_mix"])
        method = rng.choice(methods, n, p=np.array(
            list(p["method_mix"].values())) / sum(p["method_mix"].values()))
        protos = list(p["protocol_mix"])
        proto = rng.choice(protos, n, p=np.array(
            list(p["protocol_mix"].values())) / sum(p["protocol_mix"].values()))
        kind = rng.random(n)
        g = p["junk"]
        prefixes, suffixes = [], []
        limit = p["request_line_limit"]
        # httpd writes a quote inside a header as \", nginx as \x22.
        quote = '\\"' if self.dialect == "apache" else "\\x22"
        for i in range(n):
            host = pools.clients[cli_i[i]]
            user = "-" if r.random() >= p["user_share"] else \
                r.choice(pools.users)
            path = pools.paths[url_i[i]]
            if kind[i] < g["scanner_share"]:
                path = r.choice(SCANNER_PATHS)
            query = self._query(r, int(qlen[i])) if has_q[i] else ""
            req = "%s %s%s %s" % (method[i], path, query, proto[i])
            if len(req) > limit:
                req = req[:limit - len(proto[i]) - 1].rstrip("%") + " " + proto[i]
            code = str(status[i])
            nbytes = int(body[i])
            if code in STATUS_WITH_EMPTY_BODY or method[i] == "HEAD":
                nbytes = 0
            if kind[i] >= 1.0 - g["tls_share"]:
                # A TLS ClientHello sent to a plain-HTTP port, as httpd
                # logs it: escaped bytes where the request line would be.
                req = "\\x16\\x03\\x01\\x02\\x00\\x01\\x00\\x01\\xfc\\x03\\x03"
                code, nbytes = "400", 226
            size = ("-" if nbytes == 0 and self.dialect == "apache"
                    else str(nbytes))
            if r.random() < p["referer_share"]:
                ref = "https://%s%s%s" % (
                    p["site"], pools.paths[ref_i[i]],
                    self._query(r, int(rlen[i])))
            else:
                ref = "-"
            ua = pools.uas[ua_i[i]].replace("{q}", quote)
            prefix = "%s - %s [" % (host, user)
            suffix = '] "%s" %s %s "%s" "%s"' % (req, code, size, ref, ua)
            over = len(prefix) + 26 + len(suffix) - p["line_limit"]
            if over > 0:
                # Longer than the device's 8,191-byte line: a later cell
                # measures those (PERF.md); here the query gives way.
                head, proto_ = req.rsplit(" ", 1)
                req = head[:len(head) - over].rstrip("%") + " " + proto_
                suffix = '] "%s" %s %s "%s" "%s"' % (req, code, size, ref, ua)
            prefixes.append(prefix)
            suffixes.append(suffix)
        # Lines a deployment's rotation or a crash cuts short, and binary
        # junk: the reject table's share.
        n_bad = int(round(n * g["broken_share"]))
        for j in rng.choice(n, n_bad, replace=False):
            s = suffixes[j]
            cut = s.index('" ', s.index('] "') + 3) + 1
            suffixes[j] = s[:r.randrange(3, cut)]
        return prefixes, suffixes

    # -- lines ----------------------------------------------------------

    def clock(self, start_epoch_s, n, lines_per_s):
        """``n`` timestamps, in order, at a steady ``lines_per_s``."""
        secs = start_epoch_s + np.arange(n, dtype=np.int64) // lines_per_s
        uniq, inv = np.unique(secs, return_inverse=True)
        tz = self.p["timezone"]
        off = (1 if tz[0] == "+" else -1) * (int(tz[1:3]) * 3600 + int(tz[3:5]) * 60)
        stamps = []
        for s in uniq:
            t = np.datetime64(int(s) + off, "s").astype(object)
            stamps.append("%02d/%s/%04d:%02d:%02d:%02d %s" % (
                t.day, MONTHS[t.month - 1], t.year, t.hour, t.minute,
                t.second, tz))
        return [stamps[k] for k in inv]

    def draw(self, rng, n):
        """``n`` template indices, uniform over the pool (the pool itself
        carries the Zipf popularity)."""
        return rng.integers(0, len(self.prefixes), n)

    def lines(self, idx, stamps):
        pre = itemgetter(*idx)(self.prefixes) if len(idx) > 1 else (self.prefixes[idx[0]],)
        suf = itemgetter(*idx)(self.suffixes) if len(idx) > 1 else (self.suffixes[idx[0]],)
        return [a + t + b for a, t, b in zip(pre, stamps, suf)]

    def blob(self, idx, stamps):
        """The newline-terminated bytes of ``lines(idx, stamps)``."""
        if self._pre_b is None:
            self._pre_b = [x.encode() for x in self.prefixes]
            self._suf_b = [x.encode() + b"\n" for x in self.suffixes]
        pre = itemgetter(*idx)(self._pre_b)
        suf = itemgetter(*idx)(self._suf_b)
        stamps_b = [x.encode() for x in stamps]
        return b"".join(chain.from_iterable(zip(pre, stamps_b, suf)))
